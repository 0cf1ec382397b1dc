"""Chunked LM-head + cross-entropy: the vocab chain attacked as a
program, not as a kernel.

An LM step's largest tensor is the (N, V) logits of the tied head.  Both
entries here run the head matmul and the loss over ROW CHUNKS of the
flattened (N, E) hidden states, so the live vocab-chain temporaries are
one (chunk, V) block, its casts and reductions fuse into the products
around it, and XLA keeps its own MXU scheduling for every product (two
Pallas attacks on the chain lost to it: docs/kernels.md).

* :func:`make_chunked_lm_loss` — the MEAN loss, for ``make_train_step``.
  The reduction is the loss's own, so the one scalar cotangent that
  reaches the rows is the same for every row and a differentiated call
  takes each chunk's gradient in the pass that computes its loss
  (``jax.custom_vjp``): one loop over chunks, three products as wide as
  the vocabulary a chunk (logits, ``d hidden``, ``d table``), ``d table``
  summed in a float32 carry, nothing as wide as the vocabulary times
  the rows kept for the backward, which only scales.
* :func:`chunked_lm_head_loss` — per-row losses for any per-row
  cotangent.  ``d table`` cannot be summed before that cotangent is
  known, so each chunk is checkpointed and the backward computes its
  logits again: two loops, four such products, and the table's
  cotangent summed by the scan's transpose in the table's dtype (bf16
  in a bf16 step).

The models' ``output_hidden=True`` option pairs with both: forward
returns ``(hidden, head_table)`` and the loss owns the chain.

Measured on the v5e (GPT-2-small, 16 x 1024: 16 chunks of 1023 rows
against 50,257 x 768): in ``gpt2s-train`` the two loops were 30.07 ms of
a 105.16 ms step, at 85% of the array for their four products (ledger,
PR 35); the step alone read 105.4 ms with the two loops and 97.8 ms
with the one (my chip runs, PR 36; PERF.md has the cell's runs).
``kernels.dispatch.lm_head_loss.*`` count
which of the two a traced program took (docs/observability.md).
"""
from __future__ import annotations

import math
import os
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from ...kernels.dispatch import MASKED_FILL, tally
from .softmax_xentropy import _bwd as _xent_bwd
from .softmax_xentropy import _fwd as _xent_fwd
from .softmax_xentropy import (_bwd_row, _f32, _fwd_row,
                               softmax_cross_entropy_loss)


def _chunk_rows(n, v, requested):
    """Rows per chunk.  Default: balanced chunks capped at 1024 rows
    (and ~64M logits elements for very wide heads): big enough to keep
    the (chunk, V) @ (V, E) products MXU-shaped, small enough that the
    casts and the loss fuse block-locally.  Read again on the v5e with
    the gradient taken in the forward loop (the GPT-2-small 16 x 1024
    step alone, ms a step; my chip runs, PR 36): 528 rows 107.2, 1023
    rows 97.8, 2046 rows 98.7 (the float32 ``d table`` carry is read and
    written once a chunk, which is what small chunks pay for; twice the
    rows buy nothing back).  Balanced like softmax_xentropy._block_rows
    so power-of-two row counts get no remainder chunk."""
    forced = requested or int(os.environ.get("APEX_TPU_LM_CHUNK_ROWS", "0"))
    if forced > 0:
        return min(forced, n)
    cap = max(1, min(n, 1024, (1 << 26) // max(v, 1)))
    if cap >= n:
        return n
    return math.ceil(n / math.ceil(n / cap))


def _counted_row_losses_fwd(logits, labels, smoothing, padding_idx,
                            half_to_float):
    # a custom_vjp's forward rule is traced only where somebody
    # differentiates: that is where the checkpointed path is counted
    tally("lm_head_loss", "checkpointed")
    return _xent_fwd(logits, labels, smoothing, padding_idx, half_to_float)


# softmax_cross_entropy_loss itself (same primal, same rules, same name
# in the jaxpr), with the counter on its forward rule
_row_losses = jax.custom_vjp(softmax_cross_entropy_loss.fun,
                             nondiff_argnums=(2, 3, 4))
_row_losses.defvjp(_counted_row_losses_fwd, _xent_bwd)


def _chunk_logits(xc, head_weight, logical_vocab):
    """(chunk, E) rows against the (V, E) table in the rows' dtype, pad
    columns of a lane-padded head at MASKED_FILL as the model's
    ``_mask_pad_logits`` would put them."""
    logits = jnp.matmul(xc, head_weight.T.astype(xc.dtype))
    if logical_vocab is not None and logical_vocab < head_weight.shape[0]:
        cols = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
        logits = jnp.where(cols < logical_vocab, logits,
                           jnp.asarray(MASKED_FILL, logits.dtype))
    return logits


def _as_rows(hidden, labels, who):
    """(..., E) activations and their labels as (n, E) rows and (n,)
    int32 labels."""
    lead = hidden.shape[:-1]
    if labels.shape != lead:
        raise ValueError(
            f"{who}: labels shape {labels.shape} must equal hidden's "
            f"leading shape {lead}")
    n = math.prod(lead)
    return (hidden.reshape(n, hidden.shape[-1]),
            labels.reshape(n).astype(jnp.int32))


def _in_chunks(x2d, lab, chunk, padding_idx):
    """(n, E) rows and their labels as (k, chunk, E) and (k, chunk).  Rows
    added to fill the last chunk carry ``padding_idx``: zero loss, zero
    gradient, and the callers slice them off again."""
    n, e = x2d.shape
    k = math.ceil(n / chunk)
    n_p = k * chunk
    if n_p != n:
        x2d = jnp.pad(x2d, ((0, n_p - n), (0, 0)))
        lab = jnp.pad(lab, (0, n_p - n), constant_values=padding_idx)
    return x2d.reshape(k, chunk, e), lab.reshape(k, chunk)


def chunked_lm_head_loss(hidden, head_weight, labels, smoothing=0.0,
                         padding_idx=-100, logical_vocab=None,
                         chunk_rows=None):
    """Per-row cross-entropy of ``hidden @ head_weight.T`` computed and
    differentiated chunkwise — the (N, V) logits never materialize
    whole.

    hidden: (..., E) activations (any leading shape; flattened to rows).
    head_weight: (V, E) — the tied embedding table or an untied
        ``lm_head.weight`` (both store vocab-major).
    labels: integer targets, shape == hidden.shape[:-1]; rows whose
        label equals ``padding_idx`` contribute zero loss and gradient.
    logical_vocab: with a lane-padded head (GptModel
        ``pad_vocab_multiple``), the logical vocab size; pad columns are
        masked to MASKED_FILL before the loss exactly as the model's
        ``_mask_pad_logits`` would, and mask-aware smoothing keeps
        smoothed losses exact.
    chunk_rows: rows per chunk (default: balanced chunks of at most 1024
        rows; APEX_TPU_LM_CHUNK_ROWS overrides).

    Returns per-row losses with hidden's leading shape, f32.  The
    cotangent is any per-row vector, so the table's gradient cannot be
    summed before the backward knows it: each chunk is checkpointed and
    its logits are computed again there (four vocabulary-wide products a
    chunk, two loops).  A caller that wants the MEAN and its gradient
    takes :func:`make_chunked_lm_loss`, which needs three and one.
    """
    x2d, lab = _as_rows(hidden, labels, "chunked_lm_head_loss")
    n = x2d.shape[0]
    chunk = _chunk_rows(n, head_weight.shape[0], chunk_rows)

    def body(args):
        xc, lc = args                                   # (chunk, E), (chunk,)
        return _row_losses(_chunk_logits(xc, head_weight, logical_vocab),
                           lc, smoothing, padding_idx, True)

    if chunk >= n:
        losses = body((x2d, lab))
    else:
        # checkpoint: the (chunk, V) logits are recomputed in the
        # backward instead of saved — the scan carries no vocab-sized
        # residuals, and head_weight's cotangent accumulates across
        # chunks through the scan transpose
        losses = lax.map(jax.checkpoint(body),
                         _in_chunks(x2d, lab, chunk, padding_idx))
        losses = losses.reshape(-1)[:n]
    return losses.reshape(hidden.shape[:-1])


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _mean_lm_head_loss(x2d, head_weight, lab, smoothing, padding_idx,
                       logical_vocab, chunk_rows):
    """Mean over ALL rows of the chunked head loss.  Nobody
    differentiates this rule: it computes the loss alone."""
    return jnp.mean(chunked_lm_head_loss(
        x2d, head_weight, lab, smoothing=smoothing, padding_idx=padding_idx,
        logical_vocab=logical_vocab, chunk_rows=chunk_rows))


def _mean_lm_head_loss_fwd(x2d, head_weight, lab, smoothing, padding_idx,
                           logical_vocab, chunk_rows):
    """The loss and, in the same pass over a chunk, its gradient for a
    cotangent of 1 on every row's loss (the one scalar that reaches a
    mean is applied in the backward).  A chunk: logits, the rows' lse and
    loss in float32, ``d logits = softmax - q`` straight away in the
    compute dtype, and the two products that consume it.  The residuals
    are the UNSCALED float32 sums ``d hidden`` (n, E) and ``d table``
    (V, E): 1/n and the caller's scale multiply them after the products,
    so no factor that a loss scale was chosen to offset meets a half
    value."""
    tally("lm_head_loss", "grad_with_forward")
    n, e = x2d.shape
    v = head_weight.shape[0]
    chunk = _chunk_rows(n, v, chunk_rows)
    w = head_weight.astype(x2d.dtype)
    one = jnp.ones((), _f32)

    def step(dw, args):
        xc, lc = args
        logits = _chunk_logits(xc, w, logical_vocab)
        losses, lse = jax.vmap(
            lambda row, l: _fwd_row(row, l, smoothing, padding_idx))(
                logits, lc)
        dlogits = jax.vmap(
            lambda row, s, l: _bwd_row(row, s, l, one, smoothing,
                                       padding_idx, logits.dtype))(
                logits, lse, lc)
        dxc = lax.dot_general(dlogits, w, (((1,), (0,)), ((), ())),
                              preferred_element_type=_f32)
        dw = dw + lax.dot_general(dlogits, xc, (((0,), (0,)), ((), ())),
                                  preferred_element_type=_f32)
        return dw, (losses, dxc)

    dw, (losses, dx) = lax.scan(step, jnp.zeros((v, e), _f32),
                                _in_chunks(x2d, lab, chunk, padding_idx))
    loss = jnp.mean(losses.reshape(-1)[:n])
    # the operands' dtypes ride along as empty arrays
    return loss, (dx.reshape(-1, e)[:n], dw,
                  jnp.zeros((0,), x2d.dtype),
                  jnp.zeros((0,), head_weight.dtype))


def _mean_lm_head_loss_bwd(smoothing, padding_idx, logical_vocab,
                           chunk_rows, res, g):
    dx, dw, x_like, w_like = res
    scale = g.astype(_f32) / dx.shape[0]
    return ((scale * dx).astype(x_like.dtype),
            (scale * dw).astype(w_like.dtype), None)


_mean_lm_head_loss.defvjp(_mean_lm_head_loss_fwd, _mean_lm_head_loss_bwd)


def make_chunked_lm_loss(vocab_size=None, smoothing=0.0, padding_idx=-100,
                         shift=True, chunk_rows=None):
    """Loss-fn factory for ``make_train_step`` over an
    ``output_hidden=True`` LM: ``loss_fn((hidden, table), ids)`` computes
    the next-token (``shift=True``) or aligned chunked head loss.
    ``vocab_size``: the LOGICAL vocab for lane-padded heads (None: the
    table's full height).

    The mean is over ALL rows, those labelled ``padding_idx`` included
    (they add zero to the sum and one to the divisor; torch's ``mean``
    divides by the rows that count).

    Because the reduction is the loss's own, the one scalar cotangent
    that reaches the rows is known to be the same for every row, and a
    differentiated call takes each chunk's gradient in the pass that
    computes its loss (:func:`_mean_lm_head_loss_fwd`): one loop and
    three vocabulary-wide products a chunk, no logits kept and none
    computed twice."""
    def loss_fn(out, ids):
        hidden, table = out
        if shift:
            hidden = hidden[:, :-1]
            ids = ids[:, 1:]
        x2d, lab = _as_rows(hidden, ids, "make_chunked_lm_loss")
        return _mean_lm_head_loss(x2d, table, lab, smoothing, padding_idx,
                                  vocab_size, chunk_rows)
    return loss_fn
