"""Pallas TPU layer-norm kernels.

TPU re-design of the reference's ``fused_layer_norm_cuda`` extension
(csrc/layer_norm_cuda.cpp:133-241, csrc/layer_norm_cuda_kernel.cu): forward
returns ``(out, mean, invvar)`` with fp32 statistics regardless of input
dtype; backward consumes the saved stats and returns ``dx[, dgamma, dbeta]``.

Kernel layout: rows (the product of non-normalized dims) are blocked over a
1-D sequential grid; the whole normalized dim sits in the lane dimension of
one VMEM block, so per-row stats are a single in-register reduction (no
Welford needed — unlike the CUDA kernel we never split a row across blocks).
``dgamma``/``dbeta`` are accumulated across grid steps into one (1, N)
output block, relying on the TPU grid's sequential execution order.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_f32 = jnp.float32


def _block_rows(rows: int, n: int) -> int:
    """Rows per block: ~512K fp32 elements of x per block, sublane-aligned,
    then balanced across the grid so row padding is bounded by 15 rows
    (e.g. rows=528 gets 2x272-row blocks, not 2x512)."""
    bm = max(16, min(512, (1 << 19) // max(n, 1) // 16 * 16))
    nblocks = -(-rows // bm)
    return min(bm, _round_up(-(-rows // nblocks), 16))


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _fwd_kernel(x_ref, *refs, eps, affine):
    if affine:
        w_ref, b_ref, y_ref, mean_ref, rstd_ref = refs
    else:
        y_ref, mean_ref, rstd_ref = refs
    x = x_ref[...].astype(_f32)
    mean = jnp.mean(x, axis=1, keepdims=True)
    xc = x - mean
    var = jnp.mean(xc * xc, axis=1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    y = xc * rstd
    if affine:
        y = y * w_ref[...].astype(_f32) + b_ref[...].astype(_f32)
    y_ref[...] = y.astype(y_ref.dtype)
    mean_ref[...] = mean
    rstd_ref[...] = rstd


def _bwd_kernel(g_ref, x_ref, mean_ref, rstd_ref, *refs, affine):
    if affine:
        w_ref, dx_ref, dw_ref, db_ref = refs
    else:
        (dx_ref,) = refs
    g = g_ref[...].astype(_f32)
    xhat = (x_ref[...].astype(_f32) - mean_ref[...]) * rstd_ref[...]
    gh = g * w_ref[...].astype(_f32) if affine else g
    c1 = jnp.mean(gh, axis=1, keepdims=True)
    c2 = jnp.mean(gh * xhat, axis=1, keepdims=True)
    dx_ref[...] = ((gh - c1 - xhat * c2) * rstd_ref[...]).astype(dx_ref.dtype)
    if affine:
        @pl.when(pl.program_id(0) == 0)
        def _init():
            dw_ref[...] = jnp.zeros_like(dw_ref)
            db_ref[...] = jnp.zeros_like(db_ref)
        dw_ref[...] += jnp.sum(g * xhat, axis=0, keepdims=True)
        db_ref[...] += jnp.sum(g, axis=0, keepdims=True)


def ln_forward(x2d, weight, bias, eps, interpret=False):
    """x2d (rows, N); weight/bias (N,) or None. → (y, mean, rstd), stats
    fp32 with shape (rows, 1)."""
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
        args += [weight.reshape(1, n), bias.reshape(1, n)]
        in_specs += [vec_spec, vec_spec]
    y, mean, rstd = pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps, affine=affine),
        grid=(rows_p // bm,),
        in_specs=in_specs,
        out_specs=[row_spec, stat_spec, stat_spec],
        out_shape=[
            jax.ShapeDtypeStruct((rows_p, n), x2d.dtype),
            jax.ShapeDtypeStruct((rows_p, 1), _f32),
            jax.ShapeDtypeStruct((rows_p, 1), _f32),
        ],
        interpret=interpret,
        name="layer_norm_fwd",
    )(*args)
    return y[:rows], mean[:rows], rstd[:rows]


def ln_backward(g2d, x2d, mean, rstd, weight, interpret=False):
    """→ dx (and, when affine, dgamma/dbeta in fp32, shape (N,))."""
    rows, n = x2d.shape
    affine = weight is not None
    bm = _block_rows(rows, n)
    rows_p = _round_up(rows, bm)
    if rows_p != rows:
        # zero-padded g rows contribute nothing to dgamma/dbeta
        g2d = jnp.pad(g2d, ((0, rows_p - rows), (0, 0)))
        x2d = jnp.pad(x2d, ((0, rows_p - rows), (0, 0)))
        mean = jnp.pad(mean, ((0, rows_p - rows), (0, 0)))
        rstd = jnp.pad(rstd, ((0, rows_p - rows), (0, 0)), constant_values=1.0)
    row_spec = pl.BlockSpec((bm, n), lambda i: (i, 0))
    stat_spec = pl.BlockSpec((bm, 1), lambda i: (i, 0))
    vec_spec = pl.BlockSpec((1, n), lambda i: (0, 0))
    args = [g2d, x2d, mean, rstd]
    in_specs = [row_spec, row_spec, stat_spec, stat_spec]
    out_specs = [row_spec]
    out_shape = [jax.ShapeDtypeStruct((rows_p, n), x2d.dtype)]
    if affine:
        args.append(weight.reshape(1, n))
        in_specs.append(vec_spec)
        out_specs += [vec_spec, vec_spec]
        out_shape += [jax.ShapeDtypeStruct((1, n), _f32)] * 2
    outs = pl.pallas_call(
        functools.partial(_bwd_kernel, affine=affine),
        grid=(rows_p // bm,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        name="layer_norm_bwd",
    )(*args)
    if affine:
        dx, dw, db = outs
        return dx[:rows], dw.reshape(n), db.reshape(n)
    return (outs[0][:rows],)
