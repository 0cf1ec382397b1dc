"""Label-smoothed softmax cross-entropy with max_log_sum_exp residual —
TPU-native equivalent of ``apex.contrib.xentropy.SoftmaxCrossEntropyLoss``
(apex/contrib/xentropy/softmax_xentropy.py:4-28 over ``xentropy_cuda``,
apex/contrib/csrc/xentropy/xentropy_kernel.cu).

The extension's point is memory: the forward saves only the per-row
``max_log_sum_exp`` (one scalar per sample) instead of the full softmax; the
backward reconstructs probabilities as ``exp(logit - lse)``
(xentropy_kernel.cu:428-432: grad = softmax - ((1-s)·onehot + s/C)).  The
same residual contract here via ``jax.custom_vjp``.

Loss semantics (xentropy_kernel.cu:404-410): with smoothing s and C classes,
``loss_i = lse_i - (1-s)·logit_i[y_i] - s·mean_j(logit_ij)`` — i.e. cross
entropy against ``q = (1-s)·onehot + s/C``.  Per-sample losses are returned
(no reduction); rows with ``label == padding_idx`` contribute zero loss and
zero gradient (softmax_xentropy.py:10,24).  One extension over the
reference: columns masked to <= -1e29 (the -1e30 masked-vocab convention —
lane-padded heads, nucleus filtering) are excluded from the smoothing term
and its divisor, so smoothing over a padded head equals the unpadded
model exactly; unmasked inputs are bit-identical to the reference
semantics.  Out-of-range labels are garbage-in: a label >= C reads the
clamped last column under jit (jax gather semantics), a negative label
other than padding_idx clamps to column 0 — neither can raise under
trace; use padding_idx for intentional ignore rows.

Memory discipline (the part the CUDA kernel gets from streaming row-blocks
through shared memory): two measures keep peak HBM bounded at LM shapes,
where a (B·S, 50257) f32 temporary is gigabytes —

* the backward never materializes the one-hot/q tensor: the smoothing term
  folds into the elementwise ``probs - s/C`` and the label column is fixed
  up with a fused iota-compare (never a scatter — see _bwd_row);
* above ``_AUTO_ELEMS`` elements (or always, when ``APEX_TPU_XENT_BLOCK_ROWS``
  is set) both passes run row-blocked under ``lax.map(batch_size=...)`` so
  only one block of f32 temporaries is live at a time.  The GPT seq-1024
  loss shape (16384, 50257) — the on-chip out-of-memory shape this guards
  against — chunks into two blocks; the
  seq-128 headline shape stays on the single-shot path.
"""
from __future__ import annotations

import math
import os
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from ...kernels.dispatch import MASKED_LOGIT_THR as _MASK_THR
from ...kernels.dispatch import pallas_mode as _pallas_mode

_f32 = jnp.float32
# Single-shot threshold, in logits elements: one f32 temporary of this size
# is ~2.1 GB.  (16GB v5e; the backward keeps ~2 block-sized f32 temps live.)
_AUTO_ELEMS = 1 << 29


def _use_kernel(mode):
    """Compiled-mode dispatch for the Pallas xentropy kernel: OFF by
    default.  The round-4 on-chip A/B measured the kernel LOSING to
    XLA's own fusion of the jnp expression at both LM loss shapes
    (0.38x at (8192, 50257), 0.74x at (16384, 50257) fwd+bwd — the
    online-softmax block sweep is VPU-bound while XLA's reduce kernels
    are tuned; unledgered run, round 4), and the GPT seq-128 headline ran
    8% slower with it engaged.  The kernel stays for parity coverage
    (interpret mode always exercises it — that mode exists to test
    kernels) and as the starting point for a future fused
    lm-head+loss kernel; APEX_TPU_XENT_KERNEL=1 forces it on-chip."""
    if mode == "interpret":
        return True
    return mode == "compiled" and \
        os.environ.get("APEX_TPU_XENT_KERNEL", "0") == "1"


def _block_rows(n, c):
    """Rows per chunk; 0 from the env means auto (single-shot when small).

    Auto blocks are BALANCED (ceil(n / n_chunks)) rather than maximal:
    when the chunk count divides ``n`` — every power-of-two LM shape —
    lax.map gets no remainder chunk, which halves the number of large
    programs XLA compiles (the remainder is a second full fwd+bwd body;
    measured ~4-minute seq-1024 compiles with it)."""
    forced = int(os.environ.get("APEX_TPU_XENT_BLOCK_ROWS", "0"))
    if forced > 0:
        return min(forced, n)
    if n * c <= _AUTO_ELEMS:
        return n
    cap = max(1, min(n, _AUTO_ELEMS // max(c, 1)))
    return math.ceil(n / math.ceil(n / cap))


@partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def softmax_cross_entropy_loss(logits, labels, smoothing=0.0, padding_idx=0,
                               half_to_float=False):
    losses, _ = _fwd_math(logits, labels, smoothing, padding_idx)
    if not half_to_float:
        losses = losses.astype(logits.dtype)
    return losses


def _fwd_row(lf_row, label, smoothing, padding_idx):
    lf = lf_row.astype(_f32)
    m = jnp.max(lf)
    lse = m + jnp.log(jnp.sum(jnp.exp(lf - m)))
    if smoothing:
        # mask-aware smoothing: columns at the -1e30 mask convention
        # (pad_vocab_multiple heads, nucleus_filter) are excluded from
        # the smoothing mean and the divisor is the VALID column count —
        # so a lane-padded head under smoothing>0 produces exactly the
        # unpadded model's loss instead of ~1e25 garbage (a raw
        # mean(lf) would average the ~-1e30 masked log-probs in).
        # Unmasked inputs never reach -1e29, so plain models are
        # untouched; smoothing==0 (static) skips all of this.
        valid = lf > _MASK_THR
        nv = jnp.maximum(jnp.sum(valid.astype(_f32)), 1.0)
        smooth_mean = jnp.sum(jnp.where(valid, lf, 0.0)) / nv
    else:
        smooth_mean = 0.0
    loss = lse - (1.0 - smoothing) * lf[label] - smoothing * smooth_mean
    return jnp.where(label == padding_idx, 0.0, loss), lse


def _rowwise(row_fn, xs, n, block_rows):
    """Apply a per-row function over stacked rows: plain vmap when a single
    block covers everything (identical HLO to hand-batched code — no scan
    wrapper on the hot path), lax.map row-blocks otherwise."""
    if block_rows >= n:
        return jax.vmap(row_fn)(xs)
    return lax.map(row_fn, xs, batch_size=block_rows)


def _fwd_math(logits, labels, smoothing, padding_idx):
    c = logits.shape[-1]
    lead = logits.shape[:-1]
    n = math.prod(lead)
    mode = _pallas_mode()
    if _use_kernel(mode):
        from ...kernels.xentropy import xent_forward
        losses, lse = xent_forward(
            logits.reshape(n, c), labels.reshape(n), smoothing,
            padding_idx, interpret=(mode == "interpret"))
        return losses.reshape(lead), lse.reshape(lead)
    losses, lse = _rowwise(
        lambda xs: _fwd_row(xs[0], xs[1], smoothing, padding_idx),
        (logits.reshape(n, c), labels.reshape(n)),
        n, _block_rows(n, c))
    return losses.reshape(lead), lse.reshape(lead)


def _fwd(logits, labels, smoothing, padding_idx, half_to_float):
    losses, lse = _fwd_math(logits, labels, smoothing, padding_idx)
    out = losses if half_to_float else losses.astype(logits.dtype)
    # residual: logits + one scalar per row — NOT the softmax
    return out, (logits, lse, labels)


def _bwd_row(lf_row, lse, label, g, smoothing, padding_idx, out_dtype):
    c = lf_row.shape[-1]
    probs = jnp.exp(lf_row.astype(_f32) - lse)
    gm = jnp.where(label == padding_idx, 0.0, g.astype(_f32))
    # label-column fixup (q's one-hot part) as an iota-compare, NOT a
    # scatter: the compare fuses into this elementwise chain, while a
    # vmapped scatter-add lowered to an XLA scatter that serialized the
    # whole (rows, vocab) grad — measured 1.6x step-time regression on
    # the seq-128 LM headlines (unledgered run, round 4).  For a padding
    # label of -1 no column compares equal, and gm is 0 anyway.
    onehot = (jax.lax.broadcasted_iota(jnp.int32, (c,), 0) == label)
    if smoothing:
        # mirror the forward's mask-aware smoothing (see _fwd_row): the
        # s/n_valid term lands only on valid columns, so dlogits on
        # masked columns is exactly 0 (probs there is exp(-1e30-lse)=0)
        lf32 = lf_row.astype(_f32)
        valid = lf32 > _MASK_THR
        nv = jnp.maximum(jnp.sum(valid.astype(_f32)), 1.0)
        smooth_term = jnp.where(valid, smoothing / nv, 0.0)
    else:
        smooth_term = 0.0
    grad = gm * (probs - smooth_term) \
        - ((1.0 - smoothing) * gm) * onehot.astype(_f32)
    return grad.astype(out_dtype)


def _bwd(smoothing, padding_idx, half_to_float, res, g):
    logits, lse, labels = res
    c = logits.shape[-1]
    n = math.prod(logits.shape[:-1])
    mode = _pallas_mode()
    if _use_kernel(mode):
        from ...kernels.xentropy import xent_backward
        lab = labels.reshape(n)
        gm = jnp.where(lab == padding_idx, 0.0,
                       g.reshape(n).astype(_f32))
        grad = xent_backward(logits.reshape(n, c), lab, lse.reshape(n),
                             gm, smoothing,
                             interpret=(mode == "interpret"))
        return grad.reshape(logits.shape), None
    grad = _rowwise(
        lambda xs: _bwd_row(xs[0], xs[1], xs[2], xs[3], smoothing,
                            padding_idx, logits.dtype),
        (logits.reshape(n, c), lse.reshape(n), labels.reshape(n),
         g.reshape(n)),
        n, _block_rows(n, c))
    return grad.reshape(logits.shape), None


softmax_cross_entropy_loss.defvjp(_fwd, _bwd)


class SoftmaxCrossEntropyLoss:
    """Reference-parity callable surface: the reference exposes a
    ``torch.autograd.Function`` used as ``SoftmaxCrossEntropyLoss.apply(...)``
    (softmax_xentropy.py:4)."""

    @staticmethod
    def apply(logits, labels, smoothing=0.0, padding_idx=0,
              half_to_float=False):
        return softmax_cross_entropy_loss(logits, labels, smoothing,
                                          padding_idx, half_to_float)
