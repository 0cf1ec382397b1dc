"""Sequence/context parallelism: ring attention and Ulysses all-to-all.

The reference has no long-context parallelism (SURVEY.md §5 — its only
attention is the single-device fused MHA in apex/contrib/multihead_attn/);
on TPU long-context is first-class, so this module provides the two standard
sequence-parallel schemes over a mesh axis, both designed around ICI:

* ``ring_attention`` — the sequence stays sharded; K/V blocks rotate around
  the ring via ``lax.ppermute`` while each device folds one block per step
  into a numerically-stable online-softmax accumulator (running logsumexp
  merge, the same math as the Pallas flash kernel's k-sweep in
  apex_tpu/ops/pallas/attention.py, lifted one level up to the mesh).  The
  loop is unrolled over the (static) axis size for rings up to
  ``UNROLL_LIMIT`` (env ``APEX_TPU_RING_UNROLL_LIMIT``, default 8) so XLA's
  latency-hiding scheduler overlaps each step's ppermute with the previous
  step's block compute — the ring-attention trick, no hand-rolled double
  buffering.  Larger rings fall back to ``lax.fori_loop`` to keep the HLO
  O(1) per pass (an unrolled 256-ring would emit O(n^2) comm ops).
  Memory per device is O(S_local); sequence length scales linearly with the
  ring size.  The backward is a second ring pass in which dK/dV accumulators
  travel *with* their K/V blocks; after a full cycle each lands back on the
  block's owner.

* ``ulysses_attention`` — all-to-all sequence parallelism: heads are
  scattered over the axis while the sequence is gathered
  (``lax.all_to_all``), each device runs ordinary full-sequence attention on
  H/n heads (the Pallas flash kernel when enabled), and a second all-to-all
  restores the sequence sharding.  Differentiable for free (all_to_all has a
  transpose); preferred when H ≥ axis size and the per-device full sequence
  fits.

Both are meant to be called *inside* ``shard_map``/``pjit`` with q/k/v
sharded on the sequence axis, layout (B, H, S_local, D); both consume the
per-chunk kernels of ops/pallas/attention.py under the same
``pallas_mode()`` dispatch (compiled on TPU, interpret for kernel tests,
jnp fallback otherwise).
"""
from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp
import numpy as _np
from jax import lax

from ..kernels import attention as _k

_f32 = jnp.float32
_NEG = -1e30


def _chunk_bias(sq, sk, q_off, k_off, causal):
    """Additive (1, sq, sk) bias masking global-causal order for a K/V chunk
    at global key offset ``k_off`` against queries at ``q_off``."""
    if not causal:
        return None
    rows = q_off + lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
    cols = k_off + lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
    return jnp.where(rows >= cols, 0.0, _NEG).astype(_f32)[None]


def _chunk_fwd(q3, k3, v3, bias, scale, mode, dropout_p=0.0, seed=None,
               q_off=0, k_off=0):
    """One attention block → (normalized out, logsumexp).  Finite masking
    (-1e30) keeps every lse finite, which the merge relies on.

    Dropout uses the kernel's counter-based hash mask at GLOBAL
    coordinates (``q_off``/``k_off`` shift this chunk's rows/cols): the
    chunk's softmax sum ``l`` stays undropped, so the lse-merge across
    chunks reconstructs exactly dropout(P_global) @ V — bit-consistent
    masking with the single-device kernel."""
    if mode is not None:
        return _k.flash_attention_fwd(q3, k3, v3, bias, scale, False,
                                      interpret=(mode == "interpret"),
                                      dropout_p=dropout_p,
                                      dropout_seed=seed,
                                      dropout_row_off=q_off,
                                      dropout_col_off=k_off)
    s = jnp.einsum("bqd,bkd->bqk", q3.astype(_f32),
                   k3.astype(_f32)) * scale
    if bias is not None:
        s = s + bias
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)   # undropped: full softmax sum
    pn = p
    if dropout_p > 0.0:
        pn = p * _k.dropout_keep_reference(
            q3.shape[0], q3.shape[1], k3.shape[1], seed, dropout_p,
            row_off=q_off, col_off=k_off)
    out = jnp.einsum("bqk,bkd->bqd", pn, v3.astype(_f32)) / l
    return out.astype(q3.dtype), (m + jnp.log(l))[..., 0]


def _chunk_bwd(q3, k3, v3, bias, out, lse, g, scale, mode,
               dropout_p=0.0, seed=None, q_off=0, k_off=0):
    """Block gradients against the *global* (out, lse): p = exp(s - lse)
    already carries the full-softmax normalization, so per-chunk calls sum
    to the exact full-attention gradient.  With dropout, delta already
    includes the mask (it derives from the dropped ``out``); dv sees the
    dropped probs and dp routes through the multiplier — same regenerated
    global-coordinate mask as the forward."""
    if mode is not None:
        return _k.flash_attention_bwd(q3, k3, v3, bias, out, lse, g, scale,
                                      False, interpret=(mode == "interpret"),
                                      dropout_p=dropout_p,
                                      dropout_seed=seed,
                                      dropout_row_off=q_off,
                                      dropout_col_off=k_off)
    s = jnp.einsum("bqd,bkd->bqk", q3.astype(_f32),
                   k3.astype(_f32)) * scale
    if bias is not None:
        s = s + bias
    p = jnp.exp(s - lse[..., None])
    gf = g.astype(_f32)
    delta = jnp.sum(gf * out.astype(_f32), axis=-1, keepdims=True)
    if dropout_p > 0.0:
        mult = _k.dropout_keep_reference(
            q3.shape[0], q3.shape[1], k3.shape[1], seed, dropout_p,
            row_off=q_off, col_off=k_off)
        dv = jnp.einsum("bqk,bqd->bkd", p * mult, gf)
        dp = mult * jnp.einsum("bqd,bkd->bqk", gf, v3.astype(_f32))
    else:
        dv = jnp.einsum("bqk,bqd->bkd", p, gf)
        dp = jnp.einsum("bqd,bkd->bqk", gf, v3.astype(_f32))
    ds = p * (dp - delta)
    dq = jnp.einsum("bqk,bkd->bqd", ds, k3.astype(_f32)) * scale
    dk = jnp.einsum("bqk,bqd->bkd", ds, q3.astype(_f32)) * scale
    return dq, dk, dv


def _merge(out, lse, o_r, lse_r):
    """Fold a block's (normalized out, lse) into the running pair."""
    lse_new = jnp.logaddexp(lse, lse_r)
    w_old = jnp.exp(lse - lse_new)[..., None]
    w_new = jnp.exp(lse_r - lse_new)[..., None]
    return out * w_old + o_r.astype(_f32) * w_new, lse_new


# Up to this ring size the loops are Python-unrolled: each step is separate
# HLO, letting XLA's latency-hiding scheduler overlap each ppermute hop with
# the previous block's compute.  Above it, a lax.fori_loop bounds the HLO
# size at O(1) per pass (an unrolled 256-ring would emit O(n^2)
# communication ops across fwd+bwd traces and blow up compile time).
UNROLL_LIMIT = int(os.environ.get("APEX_TPU_RING_UNROLL_LIMIT", "8"))


def _expand_kv(kv3, groups, batch):
    """(B*KVH, Sk, D) -> (B*H, Sk, D): repeat each KV head over its
    query group (kv-major, groups consecutive — the GQA head order the
    Llama family uses).  groups == 1 is the MHA no-op."""
    if groups == 1:
        return kv3
    bkv, sk, d = kv3.shape
    kv4 = kv3.reshape(batch, bkv // batch, sk, d)
    return jnp.repeat(kv4, groups, axis=1).reshape(bkv * groups, sk, d)


def _reduce_kv_grad(g3, groups, batch):
    """Transpose of :func:`_expand_kv`: sum each query group's gradient
    back onto its shared KV head."""
    if groups == 1:
        return g3
    bh, sk, d = g3.shape
    g5 = g3.reshape(batch, bh // batch // groups, groups, sk, d)
    return jnp.sum(g5, axis=2).reshape(bh // groups, sk, d)


def _ring_fwd_math(q3, k3, v3, seed, axis_name, causal, scale, mode,
                   groups, batch, dropout_p=0.0):
    n = lax.psum(1, axis_name)          # static mesh-axis size
    idx = lax.axis_index(axis_name)
    bh, sq, d = q3.shape
    sk = k3.shape[1]
    out = jnp.zeros((bh, sq, d), _f32)
    lse = jnp.full((bh, sq), -jnp.inf, _f32)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(r, out, lse, k_cur, v_cur, rotate):
        """One ring step, shared by the unrolled and fori paths; ``rotate``
        controls the trailing hop (the unrolled path elides the last one).
        GQA: the ring carries KVH-wide chunks (groups x fewer ICI bytes
        per hop) and expands at the point of use."""
        src = (idx - r) % n             # which global chunk we hold now
        bias = _chunk_bias(sq, sk, idx * sq, src * sk, causal)
        o_r, lse_r = _chunk_fwd(q3, _expand_kv(k_cur, groups, batch),
                                _expand_kv(v_cur, groups, batch), bias,
                                scale, mode, dropout_p, seed,
                                q_off=idx * sq, k_off=src * sk)
        out, lse = _merge(out, lse, o_r, lse_r)
        if rotate:
            k_cur = lax.ppermute(k_cur, axis_name, perm)
            v_cur = lax.ppermute(v_cur, axis_name, perm)
        return out, lse, k_cur, v_cur

    if n <= UNROLL_LIMIT:
        k_cur, v_cur = k3, v3
        for r in range(n):
            out, lse, k_cur, v_cur = step(r, out, lse, k_cur, v_cur,
                                          rotate=(r != n - 1))
        return out, lse

    # fori body rotates unconditionally (one extra hop total vs the
    # unrolled path; n hops return k/v to their owners, so the carry
    # stays consistent)
    out, lse, _, _ = lax.fori_loop(
        0, n, lambda r, c: step(r, *c, rotate=True), (out, lse, k3, v3))
    return out, lse


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(4, 5, 6, 7, 8, 9, 10))
def _ring(q3, k3, v3, seed, axis_name, causal, scale, mode, groups, batch,
          dropout_p):
    out, _ = _ring_fwd_math(q3, k3, v3, seed, axis_name, causal, scale,
                            mode, groups, batch, dropout_p)
    return out


def _ring_vjp_fwd(q3, k3, v3, seed, axis_name, causal, scale, mode, groups,
                  batch, dropout_p):
    out, lse = _ring_fwd_math(q3, k3, v3, seed, axis_name, causal, scale,
                              mode, groups, batch, dropout_p)
    return out, (q3, k3, v3, seed, out, lse)


def _ring_vjp_bwd(axis_name, causal, scale, mode, groups, batch, dropout_p,
                  res, g):
    q3, k3, v3, seed, out, lse = res
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    sq, sk = q3.shape[1], k3.shape[1]
    out_c = out.astype(q3.dtype)
    g_c = g.astype(q3.dtype)
    perm = [(i, (i + 1) % n) for i in range(n)]
    dq = jnp.zeros(q3.shape, _f32)
    dk_cur = jnp.zeros(k3.shape, _f32)
    dv_cur = jnp.zeros(v3.shape, _f32)

    def step(r, dq, dk_cur, dv_cur, k_cur, v_cur, rotate_kv):
        """One backward ring step (shared unrolled/fori).  dK/dV
        accumulators rotate WITH their chunk; n single-hop permutes return
        every accumulator to the chunk's owner.  K/V themselves are dead
        after the last compute — only the accumulators must take that hop,
        so the unrolled path elides the final K/V rotate (``rotate_kv``)."""
        src = (idx - r) % n
        bias = _chunk_bias(sq, sk, idx * sq, src * sk, causal)
        dq_r, dk_r, dv_r = _chunk_bwd(
            q3, _expand_kv(k_cur, groups, batch),
            _expand_kv(v_cur, groups, batch), bias, out_c, lse,
            g_c, scale, mode, dropout_p, seed,
            q_off=idx * sq, k_off=src * sk)
        dq = dq + dq_r.astype(_f32)
        dk_cur = dk_cur + _reduce_kv_grad(dk_r, groups, batch).astype(_f32)
        dv_cur = dv_cur + _reduce_kv_grad(dv_r, groups, batch).astype(_f32)
        if rotate_kv:
            k_cur = lax.ppermute(k_cur, axis_name, perm)
            v_cur = lax.ppermute(v_cur, axis_name, perm)
        dk_cur = lax.ppermute(dk_cur, axis_name, perm)
        dv_cur = lax.ppermute(dv_cur, axis_name, perm)
        return dq, dk_cur, dv_cur, k_cur, v_cur

    if n <= UNROLL_LIMIT:
        k_cur, v_cur = k3, v3
        for r in range(n):
            dq, dk_cur, dv_cur, k_cur, v_cur = step(
                r, dq, dk_cur, dv_cur, k_cur, v_cur,
                rotate_kv=(r != n - 1))
    else:
        dq, dk_cur, dv_cur, _, _ = lax.fori_loop(
            0, n, lambda r, c: step(r, *c, rotate_kv=True),
            (dq, dk_cur, dv_cur, k3, v3))
    dseed = None if seed is None else _np.zeros(_np.shape(seed),
                                                jax.dtypes.float0)
    return (dq.astype(q3.dtype), dk_cur.astype(k3.dtype),
            dv_cur.astype(v3.dtype), dseed)


_ring.defvjp(_ring_vjp_fwd, _ring_vjp_bwd)


def ring_attention(q, k, v, axis_name, causal=False, scale=None,
                   dropout_p=0.0, dropout_seed=None):
    """Ring self/cross attention over a sequence-sharded mesh axis.

    q (B, H, Sq_local, D); k/v (B, KVH, Sk_local, D) with KVH dividing H
    (GQA: the ring carries KVH-wide chunks — H/KVH x fewer ICI bytes per
    hop — and expands each chunk at the point of use; KVH == H is plain
    MHA).  All sharded on the same ``axis_name`` in rank-contiguous order
    (device i holds global rows [i*S_local, (i+1)*S_local)).  Call inside
    shard_map/pjit.  Returns the local output shard (B, H, Sq_local, D)
    in q's dtype.

    ``dropout_p`` > 0 drops attention probabilities with the counter-based
    hash mask at GLOBAL coordinates: ``dropout_seed`` (an int32 scalar)
    must be REPLICATED across the axis, and the dropped ring result is
    then bit-consistent with the single-device flash kernel under the
    same seed — sequence parallelism does not change which positions
    drop (each chunk's softmax sum stays undropped, so the lse-merge
    reconstructs exactly dropout(P_global) @ V).
    """
    if dropout_p:
        if not 0.0 <= dropout_p < 1.0:
            raise ValueError(f"dropout_p must be in [0, 1), got {dropout_p}")
        if dropout_seed is None:
            raise ValueError("dropout_p > 0 requires dropout_seed "
                             "(replicated across the axis)")
    b, h, s, d = q.shape
    h_kv = k.shape[1]
    if h % h_kv:
        raise ValueError(
            f"ring_attention: q heads ({h}) not divisible by kv heads "
            f"({h_kv})")
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    # the ring's per-chunk flash step follows the flash kernel's own rule
    # at the LOCAL chunk shape, so an sp plan whose chunks sit below the
    # win region runs the jnp chunk math instead of a losing kernel n times
    mode = _k.kernel_mode(b, h, s, k.shape[2])
    q3 = q.reshape(b * h, s, d)
    k3 = k.reshape(b * h_kv, k.shape[2], d)
    v3 = v.reshape(b * h_kv, v.shape[2], d)
    seed = None if not dropout_p else dropout_seed
    out = _ring(q3, k3, v3, seed, axis_name, causal, scale, mode,
                h // h_kv, b, dropout_p)
    return out.reshape(b, h, s, d).astype(q.dtype)


def _sp_seed_fold(seed, idx):
    """Fold a sequence-parallel shard index into a dropout seed.

    Multiply-then-avalanche, deliberately NOT the bare idx*0x9E3779B1
    xor that ``_dropout_seed`` uses for the TP axis: if a
    shard-replicated base seed reaches both folds on a TP×SP mesh
    (direct API use — the make_train_step path pre-folds its keys), two
    linear xors with the SAME constant are symmetric under (tp, sp)
    index swap, so devices (a, b) and (b, a) would draw identical mask
    streams.  The shift makes this fold non-linear; no index pair
    collides."""
    h = (idx.astype(jnp.uint32) + jnp.uint32(1)) * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 15)
    return (jnp.asarray(seed).astype(jnp.uint32) ^ h).astype(jnp.int32)


def ulysses_attention(q, k, v, axis_name, causal=False, scale=None,
                      bias=None, dropout_p=0.0, dropout_seed=None):
    """All-to-all (DeepSpeed-Ulysses style) sequence parallelism.

    q/k/v (B, H, S_local, D) sequence-sharded on ``axis_name``; H must be
    divisible by the axis size.  Two tiled all-to-alls re-shard
    heads↔sequence around an ordinary full-sequence attention (Pallas flash
    kernel under ``pallas_mode()``), so each device computes H/n complete
    heads.  Differentiable end-to-end (all_to_all transposes to itself).

    ``bias`` applies to the gathered sequence, so it must be *global*-shape
    (B|1, Sq_global|1, Sk_global) and replicated across the axis — a
    sequence-local bias shard would silently mask out non-local keys.

    ``dropout_p`` > 0: each device attends full-sequence over its OWN
    head block, so the hash-mask batch·head index is local — the seed
    folds with ``axis_index`` for decorrelated per-shard streams (the
    TP semantics, NOT the ring's bit-consistency; heads are what is
    sharded here).
    """
    from ..contrib.multihead_attn.attn_funcs import flash_attention
    n = lax.psum(1, axis_name)
    if q.shape[1] % n:
        raise ValueError(
            f"ulysses_attention: heads ({q.shape[1]}) not divisible by "
            f"sequence-parallel axis size ({n})")
    if bias is not None:
        if bias.shape[-1] != k.shape[2] * n:
            raise ValueError(
                f"ulysses_attention: bias key dim ({bias.shape[-1]}) must "
                f"equal the GLOBAL key length ({k.shape[2] * n}); pass the "
                "replicated global-shape bias, not a sequence-local shard")
        if bias.ndim >= 2 and bias.shape[-2] not in (1, q.shape[2] * n):
            raise ValueError(
                f"ulysses_attention: bias query dim ({bias.shape[-2]}) must "
                f"be 1 or the GLOBAL query length ({q.shape[2] * n})")
    # (B, H, S_loc, D) → (B, H/n, S_global, D)
    qh = lax.all_to_all(q, axis_name, split_axis=1, concat_axis=2,
                        tiled=True)
    kh = lax.all_to_all(k, axis_name, split_axis=1, concat_axis=2,
                        tiled=True)
    vh = lax.all_to_all(v, axis_name, split_axis=1, concat_axis=2,
                        tiled=True)
    seed = dropout_seed
    if dropout_p and seed is not None:
        seed = _sp_seed_fold(seed, lax.axis_index(axis_name))
    out = flash_attention(qh, kh, vh, bias=bias, causal=causal, scale=scale,
                          dropout_p=dropout_p, dropout_seed=seed)
    # back to (B, H, S_loc, D)
    return lax.all_to_all(out, axis_name, split_axis=2, concat_axis=1,
                          tiled=True)
