"""Chunked LM-head + cross-entropy: the program-level vocab-chain
attack.

The round-4 GPT profile (unledgered runs, docs/performance.md) attributes
~34 ms of the 69.5 ms seq-128 step to the vocab chain — tied-head
matmul, f32 casts of the (N, V) logits, loss, and backward — while the
same chain costs 15.9 ms in isolation; two Pallas kernel attacks on the
chain measurably lost (0.43x standalone loss, 0.69x fused lm-head+loss)
because XLA's matmuls are already near roofline.  The remaining slack
is how the chain *composes* into the step: full-size (N, V) bf16
logits, two full-size f32 cast passes, and a full-size backward all
live at once.

This module attacks composition instead of kernels: the head matmul and
the loss run over ROW CHUNKS of the flattened (N, E) hidden states
under ``jax.checkpoint``, so

* the live vocab-chain temporaries are one (chunk, V) block instead of
  (N, V) — casts and loss reductions happen block-locally where XLA
  fuses them into the matmul epilogue;
* the backward recomputes each chunk's logits flash-style (the same
  +1 recompute matmul the fused kernel paid) but keeps XLA's own MXU
  scheduling for all three matmuls;
* the head-weight gradient accumulates across chunks through the scan
  transpose in f32.

The models' ``output_hidden=True`` option pairs with this: forward
returns ``(hidden, head_table)`` and the loss owns the chain.

Measured on v5e (unledgered run, round 5): see the ``--loss-mode`` A/B
rows; this path ships as an option, with the winner of the in-step A/B
promoted to the bench default.
"""
from __future__ import annotations

import math
import os
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from ...kernels.dispatch import MASKED_FILL
from .softmax_xentropy import softmax_cross_entropy_loss


def _chunk_rows(n, v, requested):
    """Rows per chunk.  Default: balanced chunks capped at 1024 rows
    (and ~64M logits elements for very wide heads) — the v5e-measured
    optimum for both LM vocabs (unledgered run, round 5: GPT 50257 swept
    127..4064 rows, peak at 1016; Llama 32000 likewise) — big enough to
    keep the (chunk, V) @ (V, E) matmuls MXU-shaped, small enough that
    casts/loss fuse block-locally.  Balanced like
    softmax_xentropy._block_rows so power-of-two row counts get no
    remainder chunk."""
    forced = requested or int(os.environ.get("APEX_TPU_LM_CHUNK_ROWS", "0"))
    if forced > 0:
        return min(forced, n)
    cap = max(1, min(n, 1024, (1 << 26) // max(v, 1)))
    if cap >= n:
        return n
    return math.ceil(n / math.ceil(n / cap))


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
    chunk_rows: rows per chunk (default: auto ~64M logits elements;
        APEX_TPU_LM_CHUNK_ROWS overrides).

    Returns per-row losses with hidden's leading shape, f32.
    """
    e = hidden.shape[-1]
    lead = hidden.shape[:-1]
    if labels.shape != lead:
        raise ValueError(
            f"chunked_lm_head_loss: labels shape {labels.shape} must "
            f"equal hidden's leading shape {lead}")
    v = head_weight.shape[0]
    n = math.prod(lead)
    x2d = hidden.reshape(n, e)
    lab = labels.reshape(n).astype(jnp.int32)
    chunk = _chunk_rows(n, v, chunk_rows)

    def body(args):
        xc, lc = args                                   # (chunk, E), (chunk,)
        logits = jnp.matmul(xc, head_weight.T.astype(xc.dtype))
        if logical_vocab is not None and logical_vocab < v:
            cols = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
            logits = jnp.where(cols < logical_vocab, logits,
                               jnp.asarray(MASKED_FILL, logits.dtype))
        return softmax_cross_entropy_loss(logits, lc, smoothing,
                                          padding_idx, True)

    if chunk >= n:
        losses = body((x2d, lab))
    else:
        k = math.ceil(n / chunk)
        n_p = k * chunk
        if n_p != n:
            # pad rows are sliced off below; the slice transpose feeds
            # them zero cotangents, so they contribute no gradient
            x2d = jnp.pad(x2d, ((0, n_p - n), (0, 0)))
            lab = jnp.pad(lab, (0, n_p - n),
                          constant_values=padding_idx)
        # checkpoint: the (chunk, V) logits are recomputed in the
        # backward instead of saved — the scan carries no vocab-sized
        # residuals, and head_weight's cotangent accumulates across
        # chunks through the scan transpose
        losses = lax.map(jax.checkpoint(body),
                         (x2d.reshape(k, chunk, e),
                          lab.reshape(k, chunk)))
        losses = losses.reshape(n_p)[:n]
    return losses.reshape(lead)


def make_chunked_lm_loss(vocab_size=None, smoothing=0.0, padding_idx=-100,
                         shift=True, chunk_rows=None):
    """Loss-fn factory for ``make_train_step`` over an
    ``output_hidden=True`` LM: ``loss_fn((hidden, table), ids)`` computes
    the next-token (``shift=True``) or aligned chunked head loss, mean
    over rows.  ``vocab_size``: the LOGICAL vocab for lane-padded heads
    (None: the table's full height)."""
    def loss_fn(out, ids):
        hidden, table = out
        if shift:
            hidden = hidden[:, :-1]
            ids = ids[:, 1:]
        per = chunked_lm_head_loss(
            hidden, table, ids, smoothing=smoothing,
            padding_idx=padding_idx, logical_vocab=vocab_size,
            chunk_rows=chunk_rows)
        return jnp.mean(per)
    return loss_fn
