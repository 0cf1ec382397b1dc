"""Multi-tensor fused-op suite: the TPU-native equivalent of Apex's ``amp_C``
extension (reference: /root/reference/csrc/amp_C_frontend.cpp:100-119 and the
``multi_tensor_*_kernel.cu`` family).

Design notes (TPU-first, not a port):

The CUDA reference packs up to 110 raw tensor pointers into kernel launch
metadata (``csrc/multi_tensor_apply.cuh:15-130``) so a whole parameter group is
processed in a handful of launches.  Under XLA there are no launches to
amortise: each op here is a pure, jittable function over *lists* of
``jax.Array``; XLA fuses the per-tensor elementwise work into a small number of
fused loops and the whole optimizer step is usually a single executable.  The
observable semantics preserved from the reference:

* a ``noop_flag`` overflow sentinel: ``multi_tensor_scale``/``axpby`` set it on
  any non-finite value (``multi_tensor_scale_kernel.cu:69-72``) — here an
  ``int32`` scalar on device, OR-accumulated functionally.  The optimizer ops
  never *write* it (the reference kernels deliberately propagate infs/nans,
  ``multi_tensor_adam.cu:40-41``); only ``multi_tensor_sgd`` *reads* it and
  leaves params/momenta untouched when set
  (``multi_tensor_sgd_kernel.cu:46``);
* fp32 math (``MATH_T``) regardless of fp16/bf16 storage
  (``csrc/multi_tensor_adam.cu`` uses float accumulators);
* in/out dtype cross-products (fp16/bf16/fp32 in → fp16/bf16/fp32 out).

Everything returns new arrays (functional); stateful wrappers in
``apex_tpu.optimizers`` / ``apex_tpu.amp`` rebind them.
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp

_f32 = jnp.float32


def _nonfinite(x) -> jax.Array:
    """True (int32 1) if any element of x is non-finite."""
    return (~jnp.isfinite(x.astype(_f32))).any().astype(jnp.int32)


def _static_nonzero(x) -> bool:
    """Whether a scalar hyperparameter must enter the program.

    False only for a concrete Python zero; traced device scalars (the step
    cache passes lr/wd/betas as traced f32 so schedules never retrace)
    always count as nonzero and the term compiles in — multiplying by a
    runtime 0.0 is then a numeric no-op.
    """
    return not (isinstance(x, (int, float)) and x == 0.0)


def _or_flags(noop_flag, flags):
    out = noop_flag
    for f in flags:
        out = jnp.maximum(out, f)
    return out


def zero_flag() -> jax.Array:
    """Fresh overflow sentinel (the reference's ``_overflow_buf.zero_()``)."""
    return jnp.zeros((), jnp.int32)


# ---------------------------------------------------------------------------
# multi_tensor_scale — csrc/multi_tensor_scale_kernel.cu:18-101
# ---------------------------------------------------------------------------

def multi_tensor_scale(noop_flag, tensor_lists: Sequence[Sequence[jax.Array]],
                       scale):
    """out[i] = in[i] * scale, flagging non-finite inputs.

    ``tensor_lists = [ins, outs]``; ``outs`` supplies the output dtypes
    (the fp16/fp32 cross-product of the reference kernel).  Returns
    ``(noop_flag, new_outs)``.
    """
    ins, outs = tensor_lists
    new_outs, flags = [], []
    for x, o in zip(ins, outs):
        xf = x.astype(_f32)
        y = xf * jnp.asarray(scale, _f32)
        flags.append(_nonfinite(x))
        new_outs.append(y.astype(o.dtype))
    return _or_flags(noop_flag, flags), new_outs


# ---------------------------------------------------------------------------
# multi_tensor_axpby — csrc/multi_tensor_axpby_kernel.cu
# ---------------------------------------------------------------------------

def multi_tensor_axpby(noop_flag, tensor_lists, a, b, arg_to_check: int = -1):
    """out = a*x + b*y with overflow check on x (0), y (1) or both (-1)
    (reference: csrc/amp_C_frontend.cpp:22-28)."""
    xs, ys, outs = tensor_lists
    new_outs, flags = [], []
    for x, y, o in zip(xs, ys, outs):
        r = jnp.asarray(a, _f32) * x.astype(_f32) + jnp.asarray(b, _f32) * y.astype(_f32)
        if arg_to_check == 0:
            flags.append(_nonfinite(x))
        elif arg_to_check == 1:
            flags.append(_nonfinite(y))
        else:
            flags.append(jnp.maximum(_nonfinite(x), _nonfinite(y)))
        new_outs.append(r.astype(o.dtype))
    return _or_flags(noop_flag, flags), new_outs


# ---------------------------------------------------------------------------
# multi_tensor_l2norm — csrc/multi_tensor_l2norm_kernel.cu
# ---------------------------------------------------------------------------

def multi_tensor_l2norm(noop_flag, tensor_lists, per_tensor: bool = False):
    """Returns (noop_flag, total_l2_norm, per_tensor_norms-or-None).

    The reference runs a two-stage block reduction plus ``cleanup`` kernel;
    XLA's reduction codegen replaces all of that.
    """
    (xs,) = tensor_lists
    if not xs:
        z = jnp.zeros((), _f32)
        return noop_flag, z, (jnp.zeros((0,), _f32) if per_tensor else None)
    sqs = [jnp.sum(jnp.square(x.astype(_f32))) for x in xs]
    total = jnp.sqrt(functools.reduce(jnp.add, sqs))
    per = jnp.sqrt(jnp.stack(sqs)) if per_tensor else None
    return noop_flag, total, per


def multi_tensor_maxnorm(noop_flag, tensor_lists, per_tensor: bool = False):
    """Max-abs-norm variant (csrc/multi_tensor_l2norm_kernel.cu:80)."""
    (xs,) = tensor_lists
    if not xs:
        z = jnp.zeros((), _f32)
        return noop_flag, z, (jnp.zeros((0,), _f32) if per_tensor else None)
    ms = [jnp.max(jnp.abs(x.astype(_f32))) for x in xs]
    total = functools.reduce(jnp.maximum, ms)
    per = jnp.stack(ms) if per_tensor else None
    return noop_flag, total, per


# ---------------------------------------------------------------------------
# multi_tensor_sgd — csrc/multi_tensor_sgd_kernel.cu:29-278
# ---------------------------------------------------------------------------

def _use_fused(op: str, tensor_lists) -> bool:
    """Whether this group goes to the packed Pallas kernel: the kernel
    module's rule (:func:`apex_tpu.kernels.multi_tensor.kernel_mode`,
    trace-time static — interpret mode only, so a compiled program and
    the CPU without a forced mode run the per-bucket path below)."""
    if not tensor_lists or not tensor_lists[0]:
        return False
    from ..kernels.multi_tensor import kernel_mode
    return kernel_mode(op) is not None


def multi_tensor_sgd(noop_flag, tensor_lists, wd, momentum, dampening, lr,
                     nesterov: bool, first_run: bool, wd_after_momentum: bool,
                     scale=1.0):
    """Momentum SGD over lists — dispatch-gated between the per-bucket
    stacks (:func:`sgd_unfused`) and the packed Pallas kernel
    (:func:`apex_tpu.kernels.multi_tensor.fused_sgd`); see
    :func:`sgd_unfused` for the update semantics."""
    if _use_fused("sgd", tensor_lists):
        from ..kernels.multi_tensor import fused_sgd
        return fused_sgd(noop_flag, tensor_lists, wd, momentum, dampening,
                         lr, nesterov, first_run, wd_after_momentum, scale)
    return sgd_unfused(noop_flag, tensor_lists, wd, momentum, dampening,
                       lr, nesterov, first_run, wd_after_momentum, scale)


def sgd_unfused(noop_flag, tensor_lists, wd, momentum, dampening, lr,
                nesterov: bool, first_run: bool, wd_after_momentum: bool,
                scale=1.0):
    """Momentum SGD over lists.

    depth 3: ``[grads, params, momenta]`` — returns (flag, params, momenta)
    depth 4: ``[grads, master_params, momenta, model_params]`` — additionally
    writes the fp16/bf16 model copy in the same pass
    (csrc/multi_tensor_sgd_kernel.cu:14-28).  ``scale`` folds gradient
    unscaling into the update (FusedSGD + amp integration,
    apex/optimizers/fused_sgd.py:211-215).

    Honors an already-set incoming ``noop_flag``: the whole update is skipped
    and inputs are returned unchanged, matching the reference kernel's
    ``if (*noop_gmem) return;`` early exit (multi_tensor_sgd_kernel.cu:46).
    """
    depth = len(tensor_lists)
    if depth == 3:
        gs, ps, ms = tensor_lists
        model_ps = None
    elif depth == 4:
        gs, ps, ms, model_ps = tensor_lists
    else:
        raise ValueError(f"multi_tensor_sgd supports depth 3 or 4, got {depth}")

    lr = jnp.asarray(lr, _f32)
    skip = noop_flag > 0
    new_ps, new_ms, new_model = [], [], []
    for i, (g, p, m) in enumerate(zip(gs, ps, ms)):
        gf = g.astype(_f32) * jnp.asarray(scale, _f32)
        pf = p.astype(_f32)
        mf = m.astype(_f32)
        if _static_nonzero(wd) and not wd_after_momentum:
            gf = gf + wd * pf
        if momentum != 0.0:
            if first_run:
                mf = gf
            else:
                mf = momentum * mf + (1.0 - dampening) * gf
            upd = gf + momentum * mf if nesterov else mf
        else:
            upd = gf
        if _static_nonzero(wd) and wd_after_momentum:
            upd = upd + wd * pf
        pf = pf - lr * upd
        new_ps.append(jnp.where(skip, p, pf.astype(p.dtype)))
        new_ms.append(jnp.where(skip, m, mf.astype(m.dtype)))
        if model_ps is not None:
            new_model.append(jnp.where(skip, model_ps[i],
                                       pf.astype(model_ps[i].dtype)))
    if model_ps is not None:
        return noop_flag, new_ps, new_ms, new_model
    return noop_flag, new_ps, new_ms


# ---------------------------------------------------------------------------
# multi_tensor_adam — csrc/multi_tensor_adam.cu
# ---------------------------------------------------------------------------

ADAM_MODE_L2 = 0          # L2 regularisation (classic Adam)
ADAM_MODE_DECOUPLED = 1   # AdamW decoupled weight decay


def multi_tensor_adam(noop_flag, tensor_lists, lr, beta1, beta2, eps, step,
                      mode: int, bias_correction: bool, weight_decay):
    """Adam / AdamW over lists — dispatch-gated between the per-bucket
    stacks (:func:`adam_unfused`) and the packed Pallas kernel
    (:func:`apex_tpu.kernels.multi_tensor.fused_adam`); see
    :func:`adam_unfused` for the update semantics."""
    if _use_fused("adam", tensor_lists):
        from ..kernels.multi_tensor import fused_adam
        return fused_adam(noop_flag, tensor_lists, lr, beta1, beta2, eps,
                          step, mode, bias_correction, weight_decay)
    return adam_unfused(noop_flag, tensor_lists, lr, beta1, beta2, eps,
                        step, mode, bias_correction, weight_decay)


def adam_unfused(noop_flag, tensor_lists, lr, beta1, beta2, eps, step,
                 mode: int, bias_correction: bool, weight_decay):
    """Adam / AdamW over ``[grads, params, exp_avgs, exp_avg_sqs]``.

    Bias correction is computed host-side exactly as the reference does
    (csrc/multi_tensor_adam.cu:144-149) when ``step`` is a Python int, and
    on-device otherwise (so the whole train step can stay jitted).

    Like the reference kernel, deliberately propagates infs/nans rather than
    writing the noop flag (multi_tensor_adam.cu:40-41) — overflow handling is
    the loss scaler's job.
    """
    gs, ps, ms, vs = tensor_lists
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
    lr = jnp.asarray(lr, _f32)

    new_ps, new_ms, new_vs = [], [], []
    for g, p, m, v in zip(gs, ps, ms, vs):
        gf, pf = g.astype(_f32), p.astype(_f32)
        mf, vf = m.astype(_f32), v.astype(_f32)
        if mode == ADAM_MODE_L2 and _static_nonzero(weight_decay):
            gf = gf + weight_decay * pf
        mf = beta1 * mf + (1.0 - beta1) * gf
        vf = beta2 * vf + (1.0 - beta2) * gf * gf
        update = (mf / bc1) / (jnp.sqrt(vf / bc2) + eps)
        if mode == ADAM_MODE_DECOUPLED and _static_nonzero(weight_decay):
            update = update + weight_decay * pf
        pf = pf - lr * update
        new_ps.append(pf.astype(p.dtype))
        new_ms.append(mf.astype(m.dtype))
        new_vs.append(vf.astype(v.dtype))
    return noop_flag, new_ps, new_ms, new_vs


# ---------------------------------------------------------------------------
# multi_tensor_novograd — csrc/multi_tensor_novograd.cu
# ---------------------------------------------------------------------------

NOVOGRAD_MOMENT_MODE_0 = 0   # L2 on grad: g' = g/denom + wd*p folded into momentum
NOVOGRAD_MOMENT_MODE_1 = 1   # decoupled: m on raw grads, wd*p added to update


def multi_tensor_novograd(noop_flag, tensor_lists, lr, beta1, beta2, eps, step,
                          bias_correction: bool, weight_decay, grad_averaging: int,
                          moment_mode: int, norm_type: int):
    """NovoGrad over ``[grads, params, exp_avgs, grad_norms]`` where
    ``grad_norms`` holds one running second-moment norm scalar per tensor
    (apex/optimizers/fused_novograd.py:106-172).

    Norm blend (csrc/multi_tensor_novograd.cu:160-164 →
    multi_tensor_l2norm_kernel.cu cleanup_v2:198-207):
      L-2 (norm_type=2):   gn = sqrt(beta2*gn² + (1-beta2)*‖g‖²)
      L-inf (norm_type=0): gn = beta2*gn + (1-beta2)*max|g|
    Moment modes (multi_tensor_novograd.cu:97-112):
      MODE_0: g' = g/denom + wd*p;  m = b1*m + b3*g';  p -= lr*(m/bc1)
      MODE_1: m = b1*m + b3*g;      p -= lr*((m/bc1)/denom + wd*p)
    with denom = gn/bc2 + eps and bc2 = sqrt(1-beta2^step)
    (multi_tensor_novograd.cu:150-151).

    Returns (flag, new_params, new_exp_avgs, new_grad_norms).  Like the
    reference kernel, propagates infs/nans instead of writing the flag.
    """
    gs, ps, ms, grad_norms = tensor_lists
    if bias_correction:
        if isinstance(step, (int, float)):
            bc1 = 1.0 - beta1 ** step
            bc2 = (1.0 - beta2 ** step) ** 0.5
        else:
            stepf = jnp.asarray(step, _f32)
            bc1 = 1.0 - jnp.asarray(beta1, _f32) ** stepf
            bc2 = jnp.sqrt(1.0 - jnp.asarray(beta2, _f32) ** stepf)
    else:
        bc1 = bc2 = 1.0
    beta3 = (1.0 - beta1) if grad_averaging else 1.0
    lr = jnp.asarray(lr, _f32)

    new_ps, new_ms, new_norms = [], [], []
    for g, p, m, vn in zip(gs, ps, ms, grad_norms):
        gf, pf, mf = g.astype(_f32), p.astype(_f32), m.astype(_f32)
        if norm_type == 0:  # L-inf: linear blend, NOT a running max
            local = jnp.max(jnp.abs(gf))
            gn = beta2 * vn.astype(_f32) + (1.0 - beta2) * local
        else:  # L2
            local = jnp.sum(gf * gf)
            gn = jnp.sqrt(beta2 * jnp.square(vn.astype(_f32))
                          + (1.0 - beta2) * local)
        denom = gn / bc2 + eps
        if moment_mode == NOVOGRAD_MOMENT_MODE_0:
            gprime = gf / denom + weight_decay * pf
            mf = beta1 * mf + beta3 * gprime
            pf = pf - lr * (mf / bc1)
        else:
            mf = beta1 * mf + beta3 * gf
            update = (mf / bc1) / denom + weight_decay * pf
            pf = pf - lr * update
        new_ps.append(pf.astype(p.dtype))
        new_ms.append(mf.astype(m.dtype))
        new_norms.append(gn.astype(vn.dtype))
    return noop_flag, new_ps, new_ms, new_norms


# ---------------------------------------------------------------------------
# multi_tensor_lamb — csrc/multi_tensor_lamb.cu
# ---------------------------------------------------------------------------

def multi_tensor_lamb(noop_flag, tensor_lists, lr, beta1, beta2, eps, step,
                      bias_correction: bool, weight_decay, grad_averaging: int,
                      mode: int, global_grad_norm, max_grad_norm):
    """Fused LAMB over ``[grads, params, exp_avgs, exp_avg_sqs]``.

    Stage 1 (csrc/multi_tensor_lamb.cu:30-55): Adam-style update ``u`` with
    global gradient-norm clipping
    (``clipped = gnorm > max ? gnorm/max : 1``, :55).
    Stage 2 (:144-166): per-tensor trust ratio — ``ratio = lr*(‖p‖/‖u‖)``
    when both norms are nonzero, else plain ``lr`` — applied as
    ``p -= ratio * u``.  ``mode``: 0 = L2 wd inside moment update,
    1 = AdamW-style decoupled.  Propagates infs/nans (no flag writes),
    matching the commented-out noop checks at :48,:156.
    """
    gs, ps, ms, vs = tensor_lists
    if bias_correction:
        bc1 = 1.0 - beta1 ** step if isinstance(step, (int, float)) else \
            1.0 - jnp.asarray(beta1, _f32) ** jnp.asarray(step, _f32)
        bc2 = 1.0 - beta2 ** step if isinstance(step, (int, float)) else \
            1.0 - jnp.asarray(beta2, _f32) ** jnp.asarray(step, _f32)
    else:
        bc1 = bc2 = 1.0
    beta3 = (1.0 - beta1) if grad_averaging else 1.0
    lr = jnp.asarray(lr, _f32)
    gnorm = jnp.asarray(global_grad_norm, _f32)
    if max_grad_norm is not None and max_grad_norm > 0:
        clip = jnp.where(gnorm > max_grad_norm, gnorm / max_grad_norm,
                         jnp.asarray(1.0, _f32))
    else:
        clip = jnp.asarray(1.0, _f32)

    new_ps, new_ms, new_vs = [], [], []
    for g, p, m, v in zip(gs, ps, ms, vs):
        gf = g.astype(_f32) / clip
        pf, mf, vf = p.astype(_f32), m.astype(_f32), v.astype(_f32)
        if mode == ADAM_MODE_L2 and _static_nonzero(weight_decay):
            gf = gf + weight_decay * pf
        mf = beta1 * mf + beta3 * gf
        vf = beta2 * vf + (1.0 - beta2) * gf * gf
        u = (mf / bc1) / (jnp.sqrt(vf / bc2) + eps)
        if mode == ADAM_MODE_DECOUPLED and _static_nonzero(weight_decay):
            u = u + weight_decay * pf
        # stage 2: trust ratio (multi_tensor_lamb.cu:166)
        p_norm = jnp.sqrt(jnp.sum(pf * pf))
        u_norm = jnp.sqrt(jnp.sum(u * u))
        use_ratio = (p_norm != 0) & (u_norm != 0)
        ratio = jnp.where(use_ratio,
                          lr * p_norm / jnp.where(use_ratio, u_norm, 1.0), lr)
        pf = pf - ratio * u
        new_ps.append(pf.astype(p.dtype))
        new_ms.append(mf.astype(m.dtype))
        new_vs.append(vf.astype(v.dtype))
    return noop_flag, new_ps, new_ms, new_vs
