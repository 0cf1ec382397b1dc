"""Pallas TPU RMS-norm kernels.

RMSNorm (Zhang & Sennrich 2019) is the LayerNorm variant modern LLM
families (Llama et al.) use: no mean subtraction, no bias — the saved
residual is just the fp32 reciprocal RMS per row.  Same kernel layout as
layer_norm.py (the reference analogue is ``fused_layer_norm_cuda``,
csrc/layer_norm_cuda.cpp — the reference has no RMS variant; this one
exists for the Llama family): rows blocked over a 1-D sequential grid,
the whole normalized dim in the lane dimension of one VMEM block, and
``dgamma`` accumulated across grid steps relying on the TPU grid's
sequential execution order.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .layer_norm import _block_rows, _round_up

_f32 = jnp.float32


def _fwd_kernel(x_ref, *refs, eps, affine):
    if affine:
        w_ref, y_ref, rstd_ref = refs
    else:
        y_ref, rstd_ref = refs
    x = x_ref[...].astype(_f32)
    ms = jnp.mean(x * x, axis=1, keepdims=True)
    rstd = jax.lax.rsqrt(ms + eps)
    y = x * rstd
    if affine:
        y = y * w_ref[...].astype(_f32)
    y_ref[...] = y.astype(y_ref.dtype)
    rstd_ref[...] = rstd


def _bwd_kernel(g_ref, x_ref, rstd_ref, *refs, affine):
    if affine:
        w_ref, dx_ref, dw_ref = refs
    else:
        (dx_ref,) = refs
    g = g_ref[...].astype(_f32)
    xhat = x_ref[...].astype(_f32) * rstd_ref[...]
    gh = g * w_ref[...].astype(_f32) if affine else g
    # d/dx of x * rsqrt(mean(x^2)+eps): the mean(gh*xhat) term is the
    # rstd-derivative contribution (no mean-centering term, unlike LN)
    c2 = jnp.mean(gh * xhat, axis=1, keepdims=True)
    dx_ref[...] = ((gh - xhat * c2) * rstd_ref[...]).astype(dx_ref.dtype)
    if affine:
        @pl.when(pl.program_id(0) == 0)
        def _init():
            dw_ref[...] = jnp.zeros_like(dw_ref)
        dw_ref[...] += jnp.sum(g * xhat, axis=0, keepdims=True)


def rms_forward(x2d, weight, eps, interpret=False):
    """x2d (rows, N); weight (N,) or None. → (y, rstd), rstd fp32 with
    shape (rows, 1)."""
    rows, n = x2d.shape
    affine = weight is not None
    bm = _block_rows(rows, n)
    rows_p = _round_up(rows, bm)
    if rows_p != rows:
        x2d = jnp.pad(x2d, ((0, rows_p - rows), (0, 0)))
    row_spec = pl.BlockSpec((bm, n), lambda i: (i, 0))
    stat_spec = pl.BlockSpec((bm, 1), lambda i: (i, 0))
    vec_spec = pl.BlockSpec((1, n), lambda i: (0, 0))
    args = [x2d]
    in_specs = [row_spec]
    if affine:
        args.append(weight.reshape(1, n))
        in_specs.append(vec_spec)
    y, rstd = pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps, affine=affine),
        grid=(rows_p // bm,),
        in_specs=in_specs,
        out_specs=[row_spec, stat_spec],
        out_shape=[
            jax.ShapeDtypeStruct((rows_p, n), x2d.dtype),
            jax.ShapeDtypeStruct((rows_p, 1), _f32),
        ],
        interpret=interpret,
        name="rms_norm_fwd",
    )(*args)
    return y[:rows], rstd[:rows]


def rms_backward(g2d, x2d, rstd, weight, interpret=False):
    """→ dx (and, when affine, dgamma in fp32, shape (N,))."""
    rows, n = x2d.shape
    affine = weight is not None
    bm = _block_rows(rows, n)
    rows_p = _round_up(rows, bm)
    if rows_p != rows:
        # zero-padded g rows contribute nothing to dgamma
        g2d = jnp.pad(g2d, ((0, rows_p - rows), (0, 0)))
        x2d = jnp.pad(x2d, ((0, rows_p - rows), (0, 0)))
        rstd = jnp.pad(rstd, ((0, rows_p - rows), (0, 0)),
                       constant_values=1.0)
    row_spec = pl.BlockSpec((bm, n), lambda i: (i, 0))
    stat_spec = pl.BlockSpec((bm, 1), lambda i: (i, 0))
    vec_spec = pl.BlockSpec((1, n), lambda i: (0, 0))
    args = [g2d, x2d, rstd]
    in_specs = [row_spec, row_spec, stat_spec]
    out_specs = [row_spec]
    out_shape = [jax.ShapeDtypeStruct((rows_p, n), x2d.dtype)]
    if affine:
        args.append(weight.reshape(1, n))
        in_specs.append(vec_spec)
        out_specs.append(vec_spec)
        out_shape.append(jax.ShapeDtypeStruct((1, n), _f32))
    outs = pl.pallas_call(
        functools.partial(_bwd_kernel, affine=affine),
        grid=(rows_p // bm,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        name="rms_norm_bwd",
    )(*args)
    if affine:
        dx, dw = outs
        return dx[:rows], dw.reshape(n)
    return (outs[0][:rows],)
