"""Fused multi-tensor optimizer update kernels.

The ``ops.multi_tensor_*`` suite is a Python loop over tensors — XLA
fuses each bucket's elementwise chain, but every tensor is its own
fusion with its own HBM round trip and the loop body retraces per
bucket.  This module packs a whole parameter group into one
``(rows, 128)`` f32 panel (cast → ravel → concat → pad) and runs the
update as ONE Pallas kernel over a 1-D row-block grid, then unpacks,
casts back per-tensor and applies the ``noop_flag`` skip outside the
kernel — the reference CUDA design (``multi_tensor_apply.cuh`` packs
110 pointers per launch) re-expressed for TPU.

Parity is to rounding BY CONSTRUCTION: the kernel body performs the
identical elementwise op chain in the identical order as the per-bucket
loop (``ops/multi_tensor.py``), every derived scalar (1-beta, bias
corrections) is computed OUTSIDE with the exact per-bucket expression
and enters through SMEM as f32 — the same rounding a weak Python float
gets under promotion — and pack/unpack is pure data movement.  What is
left to the two compilations is fused-multiply-add contraction, so the
last bit may differ: ``tests/test_kernels.py`` holds the two paths to
2 ulps, and ``chip_smoke.py`` prints the same comparison in compiled
mode on the chip.

Dispatch (:func:`kernel_mode`): like the norm kernels (round-5 receipt:
0.93-1.03x — XLA fuses elementwise chains well on its own), the fused
update is UNPROVEN on compiled TPU, so a compiled program takes the
per-bucket XLA path; the verdict is owed an A/B (docs/kernels.md).
Interpret mode always exercises the kernel — that mode exists to test
it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import dispatch as _dispatch

_f32 = jnp.float32
_LANES = 128


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _static_nonzero(x) -> bool:
    # mirrors ops.multi_tensor._static_nonzero (imported lazily there to
    # avoid a cycle; 2 lines is cheaper than the import dance)
    return not (isinstance(x, (int, float)) and x == 0.0)


def _block_rows(rows: int) -> int:
    """Sublane-aligned row block, balanced so padding stays bounded."""
    br = min(256, _round_up(max(rows, 1), 8))
    nblocks = -(-rows // br)
    return min(br, _round_up(-(-rows // nblocks), 8))


def _pack(tensors):
    """Cast-to-f32, ravel, concat and pad into a (rows, 128) panel.

    Elementwise-update parity survives packing: concat of elementwise
    ops == elementwise op of the concat, and padded tail elements are
    sliced off at unpack.
    """
    flat = [t.astype(_f32).ravel() for t in tensors]
    total = sum(f.size for f in flat)
    rows = -(-max(total, 1) // _LANES)
    br = _block_rows(rows)
    rows_p = _round_up(rows, br)
    buf = flat[0] if len(flat) == 1 else jnp.concatenate(flat)
    pad = rows_p * _LANES - total
    if pad:
        buf = jnp.pad(buf, (0, pad))
    return buf.reshape(rows_p, _LANES), br


def _unpack(panel, tensors):
    """Slice the f32 panel back into the tensors' shapes (still f32 —
    the caller owns the dtype cast and the noop skip, exactly like the
    per-bucket loop's epilogue)."""
    flat = panel.ravel()
    out, off = [], 0
    for t in tensors:
        out.append(flat[off:off + t.size].reshape(t.shape))
        off += t.size
    return out


# ---------------------------------------------------------------------------
# SGD
# ---------------------------------------------------------------------------

# SMEM scalar slots (all f32; derived values precomputed outside)
_SGD_LR, _SGD_WD, _SGD_SCALE = 0, 1, 2


def _sgd_kernel(g_ref, p_ref, m_ref, scal_ref, np_ref, nm_ref, *,
                momentum, dampening, nesterov, first_run,
                wd_after_momentum, use_wd):
    # op order is ops.multi_tensor.multi_tensor_sgd's loop body, verbatim
    gf = g_ref[...] * scal_ref[_SGD_SCALE]
    pf = p_ref[...]
    if use_wd and not wd_after_momentum:
        gf = gf + scal_ref[_SGD_WD] * pf
    if momentum != 0.0:
        if first_run:
            mf = gf
        else:
            mf = momentum * m_ref[...] + (1.0 - dampening) * gf
        upd = gf + momentum * mf if nesterov else mf
    else:
        mf = m_ref[...]
        upd = gf
    if use_wd and wd_after_momentum:
        upd = upd + scal_ref[_SGD_WD] * pf
    np_ref[...] = pf - scal_ref[_SGD_LR] * upd
    nm_ref[...] = mf


def fused_sgd(noop_flag, tensor_lists, wd, momentum, dampening, lr,
              nesterov: bool, first_run: bool, wd_after_momentum: bool,
              scale=1.0):
    """Drop-in for ``ops.multi_tensor_sgd`` (depth 3 or 4) as one packed
    Pallas pass.  Same returns, same ``noop_flag`` skip semantics."""
    depth = len(tensor_lists)
    if depth == 3:
        gs, ps, ms = tensor_lists
        model_ps = None
    elif depth == 4:
        gs, ps, ms, model_ps = tensor_lists
    else:
        raise ValueError(f"fused_sgd supports depth 3 or 4, got {depth}")
    if not gs:
        return (noop_flag, [], [], []) if model_ps is not None else \
            (noop_flag, [], [])

    use_wd = _static_nonzero(wd)
    momentum = float(momentum)
    dampening = float(dampening)
    g_pack, br = _pack(gs)
    p_pack, _ = _pack(ps)
    m_pack, _ = _pack(ms)
    scal = jnp.stack([jnp.asarray(lr, _f32),
                      jnp.asarray(wd if use_wd else 0.0, _f32),
                      jnp.asarray(scale, _f32)])
    blk = pl.BlockSpec((br, _LANES), lambda i: (i, 0))
    new_p_pack, new_m_pack = pl.pallas_call(
        functools.partial(
            _sgd_kernel, momentum=momentum, dampening=dampening,
            nesterov=bool(nesterov), first_run=bool(first_run),
            wd_after_momentum=bool(wd_after_momentum), use_wd=use_wd),
        grid=(g_pack.shape[0] // br,),
        in_specs=[blk, blk, blk, pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=[blk, blk],
        out_shape=[jax.ShapeDtypeStruct(g_pack.shape, _f32)] * 2,
        interpret=_dispatch.pallas_mode() == "interpret",
        name="fused_sgd",
    )(g_pack, p_pack, m_pack, scal)

    skip = noop_flag > 0
    pfs = _unpack(new_p_pack, ps)
    mfs = _unpack(new_m_pack, ms)
    new_ps = [jnp.where(skip, p, pf.astype(p.dtype))
              for p, pf in zip(ps, pfs)]
    new_ms = [jnp.where(skip, m, mf.astype(m.dtype))
              for m, mf in zip(ms, mfs)]
    if model_ps is not None:
        new_model = [jnp.where(skip, mp, pf.astype(mp.dtype))
                     for mp, pf in zip(model_ps, pfs)]
        return noop_flag, new_ps, new_ms, new_model
    return noop_flag, new_ps, new_ms


# ---------------------------------------------------------------------------
# Adam / AdamW
# ---------------------------------------------------------------------------

_AD_LR, _AD_WD, _AD_B1, _AD_OMB1, _AD_B2, _AD_OMB2, _AD_EPS, \
    _AD_BC1, _AD_BC2 = range(9)


def _adam_kernel(g_ref, p_ref, m_ref, v_ref, scal_ref,
                 np_ref, nm_ref, nv_ref, *, decoupled, use_wd):
    gf = g_ref[...]
    pf = p_ref[...]
    if use_wd and not decoupled:           # ADAM_MODE_L2
        gf = gf + scal_ref[_AD_WD] * pf
    mf = scal_ref[_AD_B1] * m_ref[...] + scal_ref[_AD_OMB1] * gf
    vf = scal_ref[_AD_B2] * v_ref[...] + scal_ref[_AD_OMB2] * gf * gf
    update = (mf / scal_ref[_AD_BC1]) / (
        jnp.sqrt(vf / scal_ref[_AD_BC2]) + scal_ref[_AD_EPS])
    if use_wd and decoupled:               # ADAM_MODE_DECOUPLED
        update = update + scal_ref[_AD_WD] * pf
    np_ref[...] = pf - scal_ref[_AD_LR] * update
    nm_ref[...] = mf
    nv_ref[...] = vf


def fused_adam(noop_flag, tensor_lists, lr, beta1, beta2, eps, step,
               mode: int, bias_correction: bool, weight_decay):
    """Drop-in for ``ops.multi_tensor_adam`` as one packed Pallas pass.
    Propagates infs/nans without flag writes, like the reference."""
    gs, ps, ms, vs = tensor_lists
    if not gs:
        return noop_flag, [], [], []
    # bias correction and 1-beta computed with the EXACT per-bucket
    # expressions (host-side when step/beta are Python numbers) so the
    # f32 values entering SMEM match weak-promotion rounding bitwise
    if bias_correction:
        if isinstance(step, (int, float)):
            bc1 = 1.0 - beta1 ** step
            bc2 = 1.0 - beta2 ** step
        else:
            stepf = jnp.asarray(step, _f32)
            bc1 = 1.0 - jnp.asarray(beta1, _f32) ** stepf
            bc2 = 1.0 - jnp.asarray(beta2, _f32) ** stepf
    else:
        bc1 = bc2 = 1.0
    omb1 = 1.0 - beta1
    omb2 = 1.0 - beta2
    use_wd = _static_nonzero(weight_decay)

    g_pack, br = _pack(gs)
    p_pack, _ = _pack(ps)
    m_pack, _ = _pack(ms)
    v_pack, _ = _pack(vs)
    scal = jnp.stack([jnp.asarray(v, _f32) for v in (
        lr, weight_decay if use_wd else 0.0, beta1, omb1, beta2, omb2,
        eps, bc1, bc2)])
    blk = pl.BlockSpec((br, _LANES), lambda i: (i, 0))
    new_p, new_m, new_v = pl.pallas_call(
        functools.partial(_adam_kernel, decoupled=mode == 1,
                          use_wd=use_wd),
        grid=(g_pack.shape[0] // br,),
        in_specs=[blk, blk, blk, blk,
                  pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=[blk, blk, blk],
        out_shape=[jax.ShapeDtypeStruct(g_pack.shape, _f32)] * 3,
        interpret=_dispatch.pallas_mode() == "interpret",
        name="fused_adam",
    )(g_pack, p_pack, m_pack, v_pack, scal)

    new_ps = [pf.astype(p.dtype) for p, pf in zip(ps, _unpack(new_p, ps))]
    new_ms = [mf.astype(m.dtype) for m, mf in zip(ms, _unpack(new_m, ms))]
    new_vs = [vf.astype(v.dtype) for v, vf in zip(vs, _unpack(new_v, vs))]
    return noop_flag, new_ps, new_ms, new_vs


# ---------------------------------------------------------------------------
# Registration + the executor-dispatched eager entries
# ---------------------------------------------------------------------------


def kernel_mode(op: str):
    """The rule for ``multi_tensor_<op>``: the norm-kernel lesson
    generalized — XLA fuses elementwise chains near-roofline on its own,
    so the unmeasured fused update runs in interpret mode only and
    ``None`` (the per-bucket XLA path) is the answer everywhere else."""
    return _dispatch.choose(f"multi_tensor_{op}", compiled=False)


def _audit_tensor_lists(depth):
    import jax
    sds = jax.ShapeDtypeStruct
    f32 = jnp.float32
    return [[sds((8, 4), f32), sds((16,), f32)] for _ in range(depth)]


def _audit_flag():
    import jax
    return jax.ShapeDtypeStruct((), jnp.int32)


def _sgd_audit_programs():
    from ..ops import multi_tensor as _ops

    def _pallas(flag, tl):
        return fused_sgd(flag, tl, 0.0, 0.9, 0.0, 0.05, False, True, False)

    def _xla(flag, tl):
        return _ops.sgd_unfused(flag, tl, 0.0, 0.9, 0.0, 0.05,
                                False, True, False)

    args = (_audit_flag(), _audit_tensor_lists(3))
    return [("pallas", _pallas, args), ("xla", _xla, args)]


def _adam_audit_programs():
    from ..ops import multi_tensor as _ops

    def _pallas(flag, tl):
        return fused_adam(flag, tl, 1e-3, 0.9, 0.999, 1e-8, 1, 0,
                          True, 0.0)

    def _xla(flag, tl):
        return _ops.adam_unfused(flag, tl, 1e-3, 0.9, 0.999, 1e-8, 1, 0,
                                 True, 0.0)

    args = (_audit_flag(), _audit_tensor_lists(4))
    return [("pallas", _pallas, args), ("xla", _xla, args)]


_dispatch.register_kernel(
    "multi_tensor_sgd",
    xla_fallback="apex_tpu.ops.multi_tensor.sgd_unfused",
    doc="Packed momentum-SGD group update (fused_sgd)",
    audit_programs=_sgd_audit_programs)

_dispatch.register_kernel(
    "multi_tensor_adam",
    xla_fallback="apex_tpu.ops.multi_tensor.adam_unfused",
    doc="Packed Adam/AdamW group update (fused_adam)",
    audit_programs=_adam_audit_programs)


def multi_tensor_sgd(noop_flag, tensor_lists, wd, momentum, dampening, lr,
                     nesterov: bool, first_run: bool,
                     wd_after_momentum: bool, scale=1.0):
    """Eager executor-dispatched SGD group update: the tier decision
    becomes the Program kind (``kernel.multi_tensor_sgd.<tier>``) so
    ``step_cache.kind_stats`` pins which path ran.  Donation-safe: the
    tensor lists are donated under the one DonationPolicy.  Hyperparams
    must be Python numbers here (they join the static key)."""
    from ..ops import multi_tensor as _ops

    hyper = (float(wd), float(momentum), float(dampening), float(lr),
             bool(nesterov), bool(first_run), bool(wd_after_momentum),
             float(scale))

    def pallas_fn(flag, lists):
        return fused_sgd(flag, lists, *hyper)

    def xla_fn(flag, lists):
        return _ops.sgd_unfused(flag, lists, *hyper)

    return _dispatch.run(
        "multi_tensor_sgd", kernel_mode("sgd") is not None,
        (noop_flag, tensor_lists), pallas_fn=pallas_fn, xla_fn=xla_fn,
        static_key=hyper, donate_argnums=(1,))


def multi_tensor_adam(noop_flag, tensor_lists, lr, beta1, beta2, eps,
                      step, mode: int, bias_correction: bool,
                      weight_decay):
    """Eager executor-dispatched Adam/AdamW group update (see
    :func:`multi_tensor_sgd` for the dispatch semantics)."""
    from ..ops import multi_tensor as _ops

    hyper = (float(lr), float(beta1), float(beta2), float(eps),
             int(step), int(mode), bool(bias_correction),
             float(weight_decay))

    def pallas_fn(flag, lists):
        return fused_adam(flag, lists, *hyper)

    def xla_fn(flag, lists):
        return _ops.adam_unfused(flag, lists, *hyper)

    return _dispatch.run(
        "multi_tensor_adam", kernel_mode("adam") is not None,
        (noop_flag, tensor_lists), pallas_fn=pallas_fn, xla_fn=xla_fn,
        static_key=hyper, donate_argnums=(1,))
