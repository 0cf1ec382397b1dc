"""The vocab-chain loss as a registered kernel: fused LM-head +
cross-entropy (Pallas, :mod:`.lm_head_xent`) with the chunked XLA chain
(:mod:`apex_tpu.contrib.xentropy.chunked`) as the declared fallback.

Round-4/5 history: the fused kernel measured **0.69x** against XLA's
own lowering at (8192, 50257, 768) fwd+bwd, while the *program-level*
chunked chain won **+13-15%** in-step — so a compiled program takes the
chunked XLA path at every shape (:func:`kernel_mode`; the verdict is
older than the code and owed an A/B, docs/kernels.md).  Interpret mode
exercises the kernel (parity coverage).
"""
from __future__ import annotations

import math

import jax.numpy as jnp

from . import dispatch as _dispatch
from .lm_head_xent import _fused_kernel_path


def kernel_mode():
    """The rule: 0.69x at (n=8192, v=50257, e=768) and no known win
    region on compiled TPU, so the kernel runs in interpret mode only
    and ``None`` (the chunked XLA chain) is the answer everywhere
    else."""
    return _dispatch.choose("vocab_chain_loss", compiled=False)


def _audit_programs():
    import jax
    sds = jax.ShapeDtypeStruct
    hidden = sds((32, 16), jnp.float32)
    table = sds((40, 16), jnp.float32)
    labels = sds((32,), jnp.int32)

    def _xla(x, w, lab):
        from ..contrib.xentropy.chunked import chunked_lm_head_loss
        return chunked_lm_head_loss(x, w, lab)

    return [("pallas", _fused_kernel_path, (hidden, table, labels)),
            ("xla", _xla, (hidden, table, labels))]


_dispatch.register_kernel(
    "vocab_chain_loss",
    xla_fallback="apex_tpu.contrib.xentropy.chunked.chunked_lm_head_loss",
    doc="Fused LM-head + cross-entropy (online-softmax over vocab blocks)",
    audit_programs=_audit_programs)


def vocab_chain_loss(hidden, head_weight, labels, smoothing=0.0,
                     padding_idx=-100, logical_vocab=None,
                     chunk_rows=None):
    """Per-row LM-head cross-entropy, dispatch-gated between the fused
    Pallas kernel and the chunked XLA chain.

    Same contract as :func:`chunked_lm_head_loss` (returns f32 per-row
    losses with ``hidden``'s leading shape).  The kernel arm covers the
    plain-CE case only — smoothing or a lane-padded logical vocab
    always takes the chunked path, which handles both exactly.
    """
    # lazy: contrib.xentropy.chunked imports kernels.dispatch at module
    # top, so a module-level import here would close an import cycle
    from ..contrib.xentropy.chunked import chunked_lm_head_loss

    e = hidden.shape[-1]
    lead = hidden.shape[:-1]
    v = head_weight.shape[0]
    n = math.prod(lead)

    plain = isinstance(smoothing, (int, float)) and smoothing == 0.0
    kernel_eligible = plain and (logical_vocab is None
                                 or logical_vocab >= v)
    if kernel_eligible and kernel_mode() is not None:
        x2d = hidden.reshape(n, e)
        lab = labels.reshape(n).astype(jnp.int32)
        per = _fused_kernel_path(x2d, head_weight, lab)
        # padding rows contribute zero loss AND zero gradient —
        # the where's cotangent to the kernel branch is zero there
        per = jnp.where(lab == padding_idx, jnp.zeros_like(per), per)
        return per.reshape(lead)
    return chunked_lm_head_loss(hidden, head_weight, labels,
                                smoothing=smoothing,
                                padding_idx=padding_idx,
                                logical_vocab=logical_vocab,
                                chunk_rows=chunk_rows)
