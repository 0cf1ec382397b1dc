"""Attention functionals for contrib.multihead_attn.

``self_attn_func``/``encdec_attn_func`` mirror the reference's pure-torch
paths (apex/contrib/multihead_attn/self_multihead_attn_func.py:4-118,
encdec_multihead_attn_func.py) in jnp: fused QKV projection with the
reference's PER-HEAD INTERLEAVED weight layout (in_proj output reshaped to
(T, B·H, 3, D) — self_multihead_attn_func.py:35-38, i.e. weight rows grouped
[q_h, k_h, v_h] per head, NOT the torch [Q;K;V] block layout), batched
attention GEMMs, mask fill, softmax, dropout, output projection.

``flash_attention`` is the fast path (replacing the ``fast_*_multihead_attn``
CUDA extensions): a Pallas flash kernel on TPU
(apex_tpu/ops/pallas/attention.py), an equivalent jnp computation elsewhere.
Attention dropout rides IN-KERNEL on this path — a counter-based hash mask
regenerated in the backward (the analogue of the reference's fused Philox
dropout, csrc/multihead_attn/dropout.cuh) — so the flash path stays O(S)
memory with dropout active.  It composes with every mesh: under TP each
head-shard folds its axis index into the seed (per-rank streams); under
ring-SP the mask hashes GLOBAL coordinates from the replicated pre-shard
key, making the dropped positions bit-identical to the single-device
run; ulysses decorrelates per head-shard.  Only the materializing
'default' impl refuses dropout under TP (one shared key).  The
``_attn_with_dropout`` materializing path remains for the 'default'
impl (reference softmax.h parity).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from ...nn.functional import dropout_mask
from ...kernels import attention as _k

_f32 = jnp.float32
_NEG = -1e30


def attention_reference(q4, k4, v4, bias, causal, scale, window=None,
                        dropout_p=0.0, dropout_seed=None):
    """Plain-XLA attention, (B, H, S, D) layout; the fallback/oracle
    path.  ``window`` adds the Mistral band on top of ``causal``
    (position t sees keys in (t - window, t]).  ``dropout_p`` applies
    the SAME counter-based hash mask the Pallas kernels generate
    (ops/pallas/attention.dropout_keep_reference), so the two paths
    agree bit-for-bit on which probs drop for a given seed."""
    b, h, sq, d = q4.shape
    sk = k4.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q4.astype(_f32),
                   k4.astype(_f32)) * scale
    if bias is not None:
        s = s + bias[:, None].astype(_f32)
    if causal:
        rows = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
        keep = rows >= cols
        if window is not None:
            keep = jnp.logical_and(keep, cols > rows - window)
        s = jnp.where(keep, s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    if dropout_p > 0.0:
        mult = _k.dropout_keep_reference(b * h, sq, sk, dropout_seed,
                                         dropout_p)
        p = p * jax.lax.stop_gradient(mult).reshape(b, h, sq, sk)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v4.astype(_f32)).astype(q4.dtype)


@partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash(q4, k4, v4, bias, seed, causal, scale, interpret, window,
           dropout_p):
    out, _ = _flash_fwd_math(q4, k4, v4, bias, seed, causal, scale,
                             interpret, window, dropout_p)
    return out


def _flash_fwd_math(q4, k4, v4, bias, seed, causal, scale, interpret,
                    window, dropout_p):
    b, h, sq, d = q4.shape
    sk = k4.shape[2]
    q3 = q4.reshape(b * h, sq, d)
    k3 = k4.reshape(b * h, sk, d)
    v3 = v4.reshape(b * h, sk, d)
    bias3 = None
    if bias is not None:
        # kernel bias layout (B|1, Sq|1, Sk) broadcasts over heads by
        # repeating per head in the leading dim when per-batch
        bias3 = bias if bias.shape[0] == 1 else jnp.repeat(bias, h, axis=0)
    out3, lse = _k.flash_attention_fwd(q3, k3, v3, bias3, scale, causal,
                                       interpret=interpret, window=window,
                                       dropout_p=dropout_p,
                                       dropout_seed=seed)
    return out3.reshape(b, h, sq, d), (q3, k3, v3, bias3, out3, lse)


def _flash_vjp_fwd(q4, k4, v4, bias, seed, causal, scale, interpret, window,
                   dropout_p):
    out, res = _flash_fwd_math(q4, k4, v4, bias, seed, causal, scale,
                               interpret, window, dropout_p)
    return out, (res, q4.shape, k4.shape, bias, seed)


def _flash_vjp_bwd(causal, scale, interpret, window, dropout_p, saved, g):
    (q3, k3, v3, bias3, out3, lse), qshape, kshape, bias, seed = saved
    b, h, sq, d = qshape
    dq, dk, dv = _k.flash_attention_bwd(
        q3, k3, v3, bias3, out3, lse, g.reshape(b * h, sq, d), scale, causal,
        interpret=interpret, window=window, dropout_p=dropout_p,
        dropout_seed=seed)
    dbias = None if bias is None else jnp.zeros_like(bias)
    # int32 seed cotangent is float0 by JAX convention
    import numpy as _np

    dseed = None if seed is None else _np.zeros(_np.shape(seed),
                                                jax.dtypes.float0)
    return (dq.reshape(qshape), dk.reshape(kshape), dv.reshape(kshape),
            dbias, dseed)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def _flash_packed(lin, head_dim, causal, scale, interpret):
    """Flash attention on the QKV projection's output itself, (B, T,
    3.H.D) -> (B, T, H.D), for the calls ``kernels.attention.packed_mode``
    admits; ``lin``'s columns as ``flash_attention_packed_fwd`` reads
    them (``kernels.attention.packed_row_order``)."""
    return _k.flash_attention_packed_fwd(lin, head_dim, scale, causal,
                                         interpret)[0]


def _flash_packed_vjp_fwd(lin, head_dim, causal, scale, interpret):
    ctx, lse = _k.flash_attention_packed_fwd(lin, head_dim, scale, causal,
                                             interpret)
    return ctx, (lin, ctx, lse)


def _flash_packed_vjp_bwd(head_dim, causal, scale, interpret, saved, g):
    return (_k.flash_attention_packed_bwd(*saved, g, head_dim, scale,
                                          causal, interpret),)


_flash_packed.defvjp(_flash_packed_vjp_fwd, _flash_packed_vjp_bwd)


def flash_attention(q4, k4, v4, bias=None, causal=False, scale=None,
                    sliding_window=None, dropout_p=0.0, dropout_seed=None):
    """Fused scaled-dot-product attention, (B, H, S, D) layout.

    ``bias`` is an additive mask, broadcastable (B|1, Sq|1, Sk) — carries
    key-padding and attention masks; ``causal`` masks future timesteps
    in-kernel.  ``sliding_window`` (requires ``causal``) applies the
    Mistral band — position t sees keys in (t - window, t] — with
    fully-out-of-band blocks skipped in-kernel, so banded attention
    costs O(S·window).  Gradients flow to q/k/v only (masks are data).

    ``dropout_p`` > 0 drops attention probabilities IN-KERNEL (the
    reference's fused-dropout feature, apex/contrib/csrc/multihead_attn/
    dropout.cuh): the mask is a counter-based hash of (``dropout_seed``,
    head, row, col) regenerated in the backward — no (Sq, Sk) mask
    tensor ever exists in HBM.  The XLA fallback applies the identical
    hash mask, so dispatch does not change numerics for a given seed.
    """
    if sliding_window is not None:
        if not causal:
            raise ValueError(
                "sliding_window requires causal=True (the band is "
                "defined against the causal direction)")
        if sliding_window < 1:
            raise ValueError(
                f"sliding_window must be >= 1, got {sliding_window}")
    if dropout_p:
        if not 0.0 <= dropout_p < 1.0:
            raise ValueError(f"dropout_p must be in [0, 1), got {dropout_p}")
        if dropout_seed is None:
            raise ValueError("dropout_p > 0 requires dropout_seed (an "
                             "int32 scalar; derive one per step from the "
                             "training PRNG key)")
    if scale is None:
        scale = 1.0 / math.sqrt(q4.shape[-1])
    # the kernel module's rule: trace-time static, from the mode and the
    # shape (kernels.attention.kernel_mode)
    b, h, sq, _ = q4.shape
    mode = _k.kernel_mode(b, h, sq, k4.shape[2])
    if mode is None:
        if bias is not None:
            bias = jax.lax.stop_gradient(bias)
        return attention_reference(q4, k4, v4, bias, causal, scale,
                                   window=sliding_window,
                                   dropout_p=dropout_p,
                                   dropout_seed=dropout_seed)
    return _flash(q4, k4, v4, bias,
                  None if not dropout_p else dropout_seed,
                  causal, scale, mode == "interpret", sliding_window,
                  dropout_p)


# ---------------------------------------------------------------------------
# reference-parity functional paths (torch layout: inputs (T, B, E))
# ---------------------------------------------------------------------------

def _split_interleaved_qkv(lin, t, b, heads, head_dim):
    """(T, B, 3E) → three (B·H, T, D), reference interleaved slicing
    (self_multihead_attn_func.py:35-38)."""
    lin = lin.reshape(t, b * heads, 3, head_dim)
    q, k, v = lin[:, :, 0], lin[:, :, 1], lin[:, :, 2]
    to_bhd = lambda x: jnp.swapaxes(x, 0, 1)  # (BH, T, D)
    return to_bhd(q), to_bhd(k), to_bhd(v)


def _masks_to_bias(mask, use_time_mask, b, heads, sq, sk, dtype=_f32):
    """Reference mask semantics → additive bias (B|1, Sq|1, Sk).

    Boolean/byte masks mark EXCLUDED positions with True
    (self_multihead_attn_func.py:52-66); float masks are additive."""
    if mask is None:
        return None
    mask = jnp.asarray(mask)
    if use_time_mask:
        assert mask.ndim == 2, "Timing mask is not 2D!"
        if mask.dtype == jnp.bool_ or jnp.issubdtype(mask.dtype, jnp.integer):
            return jnp.where(mask.astype(bool), _NEG, 0.0).astype(
                _f32)[None, :, :]
        return mask.astype(_f32)[None, :, :]
    # key padding (B, Sk)
    if mask.dtype == jnp.bool_ or jnp.issubdtype(mask.dtype, jnp.integer):
        return jnp.where(mask.astype(bool), _NEG, 0.0).astype(
            _f32)[:, None, :]
    return mask.astype(_f32)[:, None, :]


def _dropout_seed(key, tp_axis=None):
    """int32 kernel seed from the step's PRNG key.  Under TP the mesh
    axis index folds in, so each head-shard draws a decorrelated mask
    stream (the reference's per-rank Philox-stream semantics: multi-rank
    dropout is statistically independent, not bitwise equal to the
    single-device run)."""
    seed = jax.random.bits(key, dtype=jnp.uint32)
    if tp_axis is not None:
        seed = seed ^ (jax.lax.axis_index(tp_axis).astype(jnp.uint32)
                       * jnp.uint32(0x9E3779B1))
    return seed.astype(jnp.int32)


def _attn_with_dropout(q3, k3, v3, bias, heads, scale, dropout_prob, key,
                       use_time_mask_causal=False):
    """Materializing attention with dropout on the probabilities — the
    default-impl math (self_multihead_attn_func.py:49-87)."""
    bh, sq, d = q3.shape
    b = bh // heads
    s = jnp.einsum("btd,bsd->bts", q3.astype(_f32),
                   k3.astype(_f32)) * scale
    if bias is not None:
        s = s.reshape(b, heads, sq, -1) + bias[:, None].astype(_f32)
        s = s.reshape(bh, sq, -1)
    if use_time_mask_causal:
        rows = jnp.arange(sq)[:, None]
        cols = jnp.arange(s.shape[-1])[None, :]
        s = jnp.where(rows >= cols, s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    if dropout_prob > 0.0:
        if key is None:
            raise ValueError("attention dropout requires a PRNG key")
        keep = 1.0 - dropout_prob
        m = dropout_mask(key, keep, p.shape)
        p = jnp.where(m, p / keep, 0.0)
    return jnp.einsum("bts,bsd->btd", p, v3.astype(_f32)).astype(q3.dtype)


def _out_projection(ctx, ow, output_biases, tensor_parallel_axis):
    """The heads-major context (.., H.D) through the output projection."""
    out = jnp.matmul(ctx, ow.T)
    if tensor_parallel_axis is not None:
        # the row-parallel reduction (Megatron g: psum fwd, identity
        # bwd): one collective for the whole column→row attention pair;
        # bias added once, after the reduction
        from ...parallel.tensor_parallel import reduce_from_tp_region
        out = reduce_from_tp_region(out, tensor_parallel_axis)
    if output_biases is not None:
        out = out + output_biases
    return out


def self_attn_func(use_time_mask, is_training, heads, scale, inputs,
                   input_weights, output_weights, input_biases=None,
                   output_biases=None, mask=None, dropout_prob=0.0,
                   key=None, use_flash=False, causal=False,
                   seq_parallel_axis=None, seq_parallel_impl="ring",
                   tensor_parallel_axis=None, sp_shared_key=None):
    """Reference signature parity (self_multihead_attn_func.py:6-10);
    ``use_flash`` selects the Pallas path (the fast_* extension analogue).
    ``causal`` applies the triangle in-kernel (no O(S^2) mask operand) —
    beyond the reference signature, for decoder models.

    ``seq_parallel_axis``: run inside shard_map with the time dim sharded
    on that mesh axis — attention rides the ring (or Ulysses all-to-all,
    per ``seq_parallel_impl``) while projections stay local.  The causal
    triangle is handled globally by the SP kernels; masks are supported
    under 'ulysses' only (pass them GLOBAL-shape and replicated).
    Attention dropout composes with BOTH impls: ring hashes global
    coordinates under the replicated pre-shard key (bit-consistent with
    the single-device run), ulysses decorrelates per head-shard.

    ``tensor_parallel_axis``: Megatron-style head sharding over a mesh
    axis.  The QKV projection is column-parallel — the interleaved weight
    layout groups rows per head, so a contiguous row block IS a head
    block — each device attends over ``heads / n_tp`` local heads, and the
    output projection is row-parallel with the single psum of the
    column→row pattern (parallel/tensor_parallel.py).  Weights stay FULL
    (replicated); each device slices its block at trace time, which XLA
    folds into the weight layout.  Composes with ``seq_parallel_axis``
    (TP shards heads, SP shards time).  Attention dropout composes with
    TP on the flash path (per-shard seed streams, ``_dropout_seed``);
    the materializing 'default' impl refuses it under TP.
    """
    t, b, e = inputs.shape
    head_dim = e // heads
    iw, ow, ib = input_weights, output_weights, input_biases
    if tensor_parallel_axis is not None:
        # shared entry protocol (f operator on the stream, head check,
        # block slicing): rows of in_proj group [q_h, k_h, v_h] per head
        # (module docstring) so a contiguous row block is a head block;
        # out_proj contracts the heads-major context so column block i
        # multiplies exactly head block i
        from ...parallel.tensor_parallel import tp_attn_begin
        (inputs,), heads, rows, (ow,) = tp_attn_begin(
            tensor_parallel_axis, heads,
            [inputs], [iw] + ([ib] if ib is not None else []), [ow])
        iw = rows[0]
        if ib is not None:
            ib = rows[1]
        e = heads * head_dim
    dropout = dropout_prob if is_training else 0.0
    packed = None
    if use_flash and seq_parallel_axis is None:
        # the rule of the packed kernels, from what the projection gives
        # (kernels.attention.packed_mode)
        packed = _k.packed_mode(
            jax.ShapeDtypeStruct((b, t, iw.shape[0]),
                                 jnp.result_type(inputs, iw)),
            head_dim, mask, causal, dropout)
    if packed is not None:
        # the kernels read q, k, v where the projection writes them and
        # write the context where the output projection reads it, their
        # blocks every row of a sequence: both projections run batch-major
        # (a (T, B, .) array is tiled over its last two axes, so a view of
        # it a sequence at a time would be a copy), and XLA carries that
        # layout through the block around them
        lin = jnp.matmul(jnp.swapaxes(inputs, 0, 1),
                         _k.packed_row_order(iw, head_dim).T)
        if ib is not None:
            lin = lin + _k.packed_row_order(ib, head_dim)
        ctx = _flash_packed(lin, head_dim, causal, scale,
                            packed == "interpret")
        return jnp.swapaxes(_out_projection(
            ctx, ow, output_biases, tensor_parallel_axis), 0, 1)
    lin = jnp.matmul(inputs, iw.T)
    if ib is not None:
        lin = lin + ib
    q3, k3, v3 = _split_interleaved_qkv(lin, t, b, heads, head_dim)
    if seq_parallel_axis is not None:
        from ...parallel.ring_attention import (ring_attention,
                                                ulysses_attention)
        if seq_parallel_impl not in ("ring", "ulysses"):
            raise ValueError(
                f"seq_parallel_impl must be 'ring' or 'ulysses', got "
                f"{seq_parallel_impl!r}")
        sp_bias = None
        if mask is not None:
            if seq_parallel_impl != "ulysses":
                raise NotImplementedError(
                    "masks under sequence parallelism require the "
                    "'ulysses' impl (each device sees the gathered global "
                    "sequence there; the ring carries no mask operand)")
            # the mask must be GLOBAL (key_padding (B, S_global) or time
            # (S_g, S_g)) and replicated across the axis; the bias derives
            # from the mask's own (global) shape, and ulysses_attention
            # validates it against the gathered lengths
            sp_bias = _masks_to_bias(mask, use_time_mask, b, heads, t, t)
        ring_seed = uly_seed = None
        if dropout > 0.0:
            # ring: the mask hashes GLOBAL coordinates, so a seed
            # replicated across the axis makes SP dropout bit-consistent
            # with the single-device kernel.  ulysses: heads are what is
            # sharded — per-shard decorrelated streams (TP semantics).
            if seq_parallel_impl == "ring":
                if sp_shared_key is None:
                    raise ValueError(
                        "ring-SP attention dropout needs the replicated "
                        "pre-shard key (sp_shared_key); model forwards "
                        "supply it via fold_shard_into_key's shared_key")
                # sp-replicated seed; under a TP x SP mesh the tp fold
                # decorrelates head shards (axis_index(tp) is constant
                # along sp, so sp-replication survives)
                ring_seed = _dropout_seed(sp_shared_key,
                                          tensor_parallel_axis)
            else:
                if key is None:
                    raise ValueError(
                        "attention dropout requires a PRNG key")
                uly_seed = _dropout_seed(key, tensor_parallel_axis)
        q4 = q3.reshape(b, heads, t, head_dim)
        k4 = k3.reshape(b, heads, t, head_dim)
        v4 = v3.reshape(b, heads, t, head_dim)
        if seq_parallel_impl == "ring":
            ctx4 = ring_attention(q4, k4, v4,
                                  axis_name=seq_parallel_axis,
                                  causal=causal, scale=scale,
                                  dropout_p=dropout,
                                  dropout_seed=ring_seed)
        else:
            ctx4 = ulysses_attention(q4, k4, v4,
                                     axis_name=seq_parallel_axis,
                                     causal=causal, scale=scale,
                                     bias=sp_bias, dropout_p=dropout,
                                     dropout_seed=uly_seed)
        ctx3 = ctx4.reshape(b * heads, t, head_dim)
    elif use_flash:
        # dropout rides IN-KERNEL (the reference fast path fuses dropout
        # the same way, apex/contrib/csrc/multihead_attn/dropout.cuh)
        bias = _masks_to_bias(mask, use_time_mask, b, heads, t, t)
        q4 = q3.reshape(b, heads, t, head_dim)
        k4 = k3.reshape(b, heads, t, head_dim)
        v4 = v3.reshape(b, heads, t, head_dim)
        seed = None
        if dropout > 0.0:
            if key is None:
                raise ValueError("attention dropout requires a PRNG key")
            seed = _dropout_seed(key, tensor_parallel_axis)
        ctx4 = flash_attention(q4, k4, v4, bias=bias, causal=causal,
                               scale=scale, dropout_p=dropout,
                               dropout_seed=seed)
        ctx3 = ctx4.reshape(b * heads, t, head_dim)
    else:
        if tensor_parallel_axis is not None and dropout > 0.0:
            raise NotImplementedError(
                "attention dropout under tensor parallelism requires the "
                "flash path (impl='fast'): the materializing impl draws "
                "its mask from one shared key, which would correlate "
                "dropout across head shards")
        bias = _masks_to_bias(mask, use_time_mask, b, heads, t, t)
        ctx3 = _attn_with_dropout(q3, k3, v3, bias, heads, scale, dropout,
                                  key, use_time_mask_causal=causal)
    ctx = jnp.swapaxes(ctx3, 0, 1).reshape(t, b, e)
    return _out_projection(ctx, ow, output_biases, tensor_parallel_axis)


def encdec_attn_func(use_time_mask, is_training, heads, scale, inputs_q,
                     inputs_kv, input_weights_q, input_weights_kv,
                     output_weights, mask=None, dropout_prob=0.0,
                     key=None, use_flash=False,
                     tensor_parallel_axis=None):
    """Encoder-decoder attention (encdec_multihead_attn_func.py): q from the
    decoder stream, interleaved (k, v) from the encoder stream.

    ``tensor_parallel_axis``: Megatron head sharding, same design as
    ``self_attn_func`` — q rows group per head and kv rows per head as
    ``[k_h, v_h]`` pairs, so contiguous row blocks are head blocks; the
    output projection is row-parallel with one reduction.  Both streams
    pass through the f operator (their gradients feed the encoder AND
    decoder stacks)."""
    tq, b, e = inputs_q.shape
    tk = inputs_kv.shape[0]
    head_dim = e // heads
    wq, wkv, ow = input_weights_q, input_weights_kv, output_weights
    if tensor_parallel_axis is not None:
        # shared entry protocol; q rows group per head, kv rows per head
        # as [k_h, v_h] pairs — contiguous row blocks are head blocks
        from ...parallel.tensor_parallel import tp_attn_begin
        (inputs_q, inputs_kv), heads, (wq, wkv), (ow,) = tp_attn_begin(
            tensor_parallel_axis, heads,
            [inputs_q, inputs_kv], [wq, wkv], [ow])
        e = heads * head_dim
    q = jnp.matmul(inputs_q, wq.T)
    kv = jnp.matmul(inputs_kv, wkv.T)
    q3 = jnp.swapaxes(q.reshape(tq, b * heads, head_dim), 0, 1)
    kv = kv.reshape(tk, b * heads, 2, head_dim)
    k3 = jnp.swapaxes(kv[:, :, 0], 0, 1)
    v3 = jnp.swapaxes(kv[:, :, 1], 0, 1)
    bias = _masks_to_bias(mask, use_time_mask, b, heads, tq, tk)
    dropout = dropout_prob if is_training else 0.0
    if use_flash:
        q4 = q3.reshape(b, heads, tq, head_dim)
        k4 = k3.reshape(b, heads, tk, head_dim)
        v4 = v3.reshape(b, heads, tk, head_dim)
        seed = None
        if dropout > 0.0:
            # in-kernel dropout, same contract as self_attn_func
            if key is None:
                raise ValueError("attention dropout requires a PRNG key")
            seed = _dropout_seed(key, tensor_parallel_axis)
        ctx4 = flash_attention(q4, k4, v4, bias=bias, causal=False,
                               scale=scale, dropout_p=dropout,
                               dropout_seed=seed)
        ctx3 = ctx4.reshape(b * heads, tq, head_dim)
    else:
        if tensor_parallel_axis is not None and dropout > 0.0:
            raise NotImplementedError(
                "attention dropout under tensor parallelism requires the "
                "flash path (impl='fast'): the materializing impl draws "
                "its mask from one shared key, which would correlate "
                "dropout across head shards")
        ctx3 = _attn_with_dropout(q3, k3, v3, bias, heads, scale, dropout,
                                  key)
    ctx = jnp.swapaxes(ctx3, 0, 1).reshape(tq, b, e)
    return _out_projection(ctx, ow, None, tensor_parallel_axis)
