"""spec_verify: the serve engine's batched draft/verify decode tick as
a measured dispatch tier.

Speculative decoding is a perf *claim* — "the draft accepts enough
tokens that one (k+1)-wide verify pass beats k+1 one-token decode
ticks" — so it registers here like any Pallas kernel and gets priced by
the same ledger machinery.  The tiers:

* **"pallas"** — the fused draft-propose + target-verify program body
  (:func:`apex_tpu.serve.kernels.build_spec_verify_fn`), committing
  1..k+1 tokens per dispatch;
* **"xla"** (the declared fallback) — the plain one-token decode
  program (:func:`apex_tpu.serve.kernels.build_decode_fn`).

Both tiers emit bitwise-identical greedy tokens (acceptance only ever
truncates to a prefix of the target's own argmax stream), so a ledger
entry's ``win`` is a pure tokens/s ratio at equal batch — measured by
``bench.py --kernels``' spec_verify probe, which times one verify
dispatch against the k+1 chained decode dispatches it replaces on a
self-draft (full-acceptance) trace.  ``ServeEngine(spec="auto")``
consults :func:`~apex_tpu.kernels.dispatch.decide` with this kernel's
fingerprint per packed bucket shape and falls back to plain decode
ticks below the win region; with no Pallas backend (CPU serving)
``decide`` says "xla" — tests and CPU benches opt in with
``spec="on"``.

This module deliberately imports nothing from ``apex_tpu.serve`` at
module level — it exists so the kernel is in :func:`catalog` whenever
``apex_tpu.kernels`` is, keeping the jaxpr verifier's "every registered
kernel, both tiers" sweep order-independent.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .dispatch import measured_threshold, register_kernel, shape_fp


def spec_verify_fp(*, b, k, s_t, s_d, dtype) -> str:
    """Ledger fingerprint for one spec-verify dispatch shape: batch
    bucket ``b``, draft depth ``k``, target/draft table
    widths ``s_t``/``s_d`` (table bucket x block_size), pool dtype.
    Built by the SAME helper at probe time (bench) and decision time
    (the engine's ``spec="auto"`` path)."""
    return shape_fp(b=int(b), k=int(k), s_t=int(s_t), s_d=int(s_d),
                    dtype=str(dtype))


def _spec_verify_probe(dims):
    """No-ledger prior: speculative verify pays when the draft proposes
    at least ``thr`` tokens per tick — at the >= 2 tokens/tick
    acceptance floor a k >= 2 draft amortizes the verify chunk's extra
    width.  A measured winning ``k`` boundary for this chip moves the
    threshold off the prior."""
    thr = float(measured_threshold("spec_verify", "k", 2))
    return thr, dims.get("k", 0) >= thr


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
    threshold_probe=_spec_verify_probe,
    doc="Batched speculative draft/verify decode tick (serve v2): "
        "fused k-step draft propose + (k+1)-wide target verify vs the "
        "plain one-token decode program it replaces; both tiers emit "
        "bitwise-identical greedy tokens, so win is pure tokens/s",
    audit_programs=_audit_programs)
