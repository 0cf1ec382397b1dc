"""spec_verify: the serve engine's batched draft/verify decode tick, in
the registry so that the jaxpr verifier traces it beside the plain
decode program it replaces.

* **"pallas"** — the fused draft-propose + target-verify program body
  (:func:`apex_tpu.serve.kernels.build_spec_verify_fn`), committing
  1..k+1 tokens per dispatch;
* **"xla"** (the declared fallback) — the plain one-token decode
  program (:func:`apex_tpu.serve.kernels.build_decode_fn`).

Both emit bitwise-identical greedy tokens (acceptance only ever
truncates to a prefix of the target's own argmax stream).  Which one
runs is not a kernel choice: a ``ServeEngine`` given a draft
speculates.

This module deliberately imports nothing from ``apex_tpu.serve`` at
module level — it exists so the kernel is in :func:`catalog` whenever
``apex_tpu.kernels`` is, keeping the jaxpr verifier's "every registered
kernel, both tiers" sweep order-independent.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .dispatch import register_kernel


def _audit_programs():
    """Both tiers traced abstractly: the fused verify body and the
    plain-decode fallback, over one tiny GPT pair (real modules — the
    bodies close over model structure; the OPERANDS stay abstract)."""
    from .. import nn as _nn
    from ..models.gpt import GptModel
    from ..serve.kernels import build_decode_fn, build_spec_verify_fn

    _nn.manual_seed(0)
    target = GptModel(vocab_size=31, hidden=16, layers=1, heads=2,
                      max_positions=32, dropout=0.0, attn_dropout=0.0)
    _nn.manual_seed(1)
    draft = GptModel(vocab_size=31, hidden=16, layers=1, heads=2,
                     max_positions=32, dropout=0.0, attn_dropout=0.0)
    target.eval()
    draft.eval()
    t_params = list(target.parameters()) + list(target.buffers())
    d_params = list(draft.parameters()) + list(draft.buffers())

    sds = jax.ShapeDtypeStruct
    i32 = jnp.int32
    bs, nblk, k, b, nb = 4, 6, 2, 2, 2
    t_vals = [sds(p.data.shape, p.data.dtype) for p in t_params]
    d_vals = [sds(p.data.shape, p.data.dtype) for p in d_params]
    pool = sds((1, 2, nblk, bs, 2 * 8), jnp.float32)  # (L,2,NB,bs,H*D)
    toks = sds((b,), i32)
    pos = sds((b,), i32)
    tab = sds((b, nb), i32)

    spec_fn = build_spec_verify_fn(target, t_params, draft, d_params,
                                   bs, nblk, k)
    dec_fn = build_decode_fn(target, t_params, bs, nblk)
    return [("pallas", spec_fn,
             (t_vals, d_vals, pool, pool, toks, pos, tab, tab)),
            ("xla", dec_fn, (t_vals, pool, toks, pos, tab))]


register_kernel(
    "spec_verify",
    xla_fallback="apex_tpu.serve.kernels.build_decode_fn",
    doc="Batched speculative draft/verify decode tick (serve v2): "
        "fused k-step draft propose + (k+1)-wide target verify vs the "
        "plain one-token decode program it replaces; both tiers emit "
        "bitwise-identical greedy tokens",
    audit_programs=_audit_programs)
