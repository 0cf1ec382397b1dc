"""Fused multi-model/multi-loss train step: the GAN iteration.

The reference exercises its multi-model amp surface through DCGAN
(examples/dcgan/main_amp.py:214-253: ``amp.initialize([netD, netG],
[optD, optG], num_losses=3)`` with per-loss ``loss_id``) on the imperative
path.  This module is the fused-path equivalent: the full alternating
iteration —

1. ``fake = netG(z)`` (one generator forward),
2. discriminator step: grads of ``d_loss_fn(netD(real), netD(sg(fake)))``
   w.r.t. D only, fused optimizer update, per-loss scaler,
3. generator step: grads of ``g_loss_fn(netD'(fake))`` w.r.t. G, flowing
   through the *updated* discriminator (the reference ordering: errG is
   computed after optimizerD.step()),

— compiles into ONE XLA executable.  XLA CSEs the two generator forwards
(same params, same z, same dropout key), so the compiled graph runs G once.
Each network has its own loss scaler and skip-step, like the reference's
per-loss scalers; an overflow in D leaves D unchanged but the G step still
runs against the old D.
"""
from __future__ import annotations

import itertools
import time
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..nn.modules import Ctx
from .step import (StepState, apply_fused_update, build_opt_update,
                   init_step_state, match_param_groups, model_vals_of,
                   _model_dtypes)


#: per-builder token in the executor program key (two GAN steps with
#: identical signatures close over different nets/losses)
_GAN_TOKENS = itertools.count()


class GanStepState(NamedTuple):
    d: StepState
    g: StepState


class GanTrainStep:
    """Built by :func:`make_gan_train_step`."""

    def __init__(self, netD, netG, optD, optG, step_fn, d_parts, g_parts,
                 init_state):
        self.netD, self.netG = netD, netG
        self.optD, self.optG = optD, optG
        self._step_fn = step_fn
        self._d_parts, self._g_parts = d_parts, g_parts
        self.state = init_state
        self.compile_s = None

    def __call__(self, real, z):
        t0 = time.perf_counter() if self.compile_s is None else None
        self.state, losses = self._step_fn(self.state, real, z)
        if t0 is not None:
            self.compile_s = time.perf_counter() - t0
        return losses

    def sync_to_objects(self):
        for (params, buffers), sub in ((self._d_parts, self.state.d),
                                       (self._g_parts, self.state.g)):
            for i, (p, v) in enumerate(zip(params, sub.model_params)):
                p.data = sub.master_params[i] if v is None else v
            for b, v in zip(buffers, sub.stats):
                b.data = v


def _net_parts(model, optimizer, half_dtype, keep_batchnorm_fp32, caller):
    params = [p for p in model.parameters() if p is not None]
    buffers = [b for b in model.buffers()]
    group_idxs = match_param_groups(optimizer, params, caller=caller)
    dtypes = _model_dtypes(model, params, half_dtype, keep_batchnorm_fp32)
    opt_update, opt_init = build_opt_update(optimizer, params, group_idxs,
                                            caller=caller)
    return params, buffers, dtypes, opt_update, opt_init


def make_gan_train_step(netD, netG, optD, optG,
                        d_loss_fn: Callable, g_loss_fn: Callable,
                        half_dtype=None,
                        keep_batchnorm_fp32: bool = True,
                        loss_scale: float | str = "dynamic",
                        scale_window: int = 2000,
                        min_loss_scale: Optional[float] = None,
                        max_loss_scale: float = 2.0 ** 24,
                        donate_state="auto",
                        lr_schedule: Optional[Callable] = None,
                        rng_seed: int = 0):
    """Build the fused GAN iteration.

    ``d_loss_fn(d_real_out, d_fake_out) -> scalar`` and
    ``g_loss_fn(d_fake_out) -> scalar`` (e.g. BCE against real/fake labels).
    The step signature is ``step(state, real_batch, z) -> (state,
    (errD, errG))``.  ``lr_schedule`` applies to both optimizers from
    each network's own step counter (as in make_train_step).
    """
    from ..runtime import executor as _executor
    # the executor's donation policy: donate on tpu/gpu, skip on cpu
    # (defensive copies — see make_train_step's donate_state doc)
    donate_state = _executor.donation.resolve(donate_state)
    d_parts = _net_parts(netD, optD, half_dtype, keep_batchnorm_fp32,
                         "make_gan_train_step(netD)")
    g_parts = _net_parts(netG, optG, half_dtype, keep_batchnorm_fp32,
                         "make_gan_train_step(netG)")
    d_params, d_buffers, d_dtypes, d_update, d_opt_init = d_parts
    g_params, g_buffers, g_dtypes, g_update, g_opt_init = g_parts

    dynamic = loss_scale == "dynamic"
    init_scale = (min(max_loss_scale, 2.0 ** 16) if dynamic
                  else float(loss_scale))

    def _run(model, params, buffers, param_vals, stats, x, key,
             training=True):
        """One pure forward; returns (out, new_stats)."""
        env = {id(p): v for p, v in zip(params, param_vals)}
        env.update({id(b): v for b, v in zip(buffers, stats)})
        stats_out = {}
        ctx = Ctx(env=env, stats_out=stats_out, training=training, key=key)
        out = model.forward(ctx, x)
        new_stats = [stats_out.get(id(b), sv)
                     for b, sv in zip(buffers, stats)]
        return out, new_stats

    def _finish_update(sub: StepState, grads, opt_update, dtypes):
        return apply_fused_update(
            sub, grads, opt_update, dtypes, dynamic=dynamic,
            init_scale=init_scale, scale_window=scale_window,
            min_loss_scale=min_loss_scale, max_loss_scale=max_loss_scale,
            lr_schedule=lr_schedule)

    def step_fn(state: GanStepState, real, z):
        d, g = state.d, state.g
        base = jax.random.PRNGKey(rng_seed)
        g_key = jax.random.fold_in(base, g.step * 2)
        # the three discriminator forwards (real, detached fake, G-step)
        # each get their own key so a D with Dropout draws independent
        # masks per call, matching the imperative path's fresh key per
        # module call
        d_base = jax.random.fold_in(base, d.step * 2 + 1)
        d_key_real = jax.random.fold_in(d_base, 0)
        d_key_fake = jax.random.fold_in(d_base, 1)
        d_key_gstep = jax.random.fold_in(d_base, 2)

        if half_dtype is not None:
            if jnp.issubdtype(real.dtype, jnp.floating):
                real = real.astype(half_dtype)
            if jnp.issubdtype(z.dtype, jnp.floating):
                z = z.astype(half_dtype)

        g_vals = model_vals_of(g)
        d_vals = model_vals_of(d)

        # 1) generator forward (no grad; CSE'd with the G-step's forward)
        fake, _ = _run(netG, g_params, g_buffers, g_vals, g.stats, z, g_key)
        fake = jax.lax.stop_gradient(fake)

        # 2) discriminator step on real + detached fake
        def d_forward(d_vals_in):
            out_r, stats1 = _run(netD, d_params, d_buffers, d_vals_in,
                                 d.stats, real, d_key_real)
            out_f, stats2 = _run(netD, d_params, d_buffers, d_vals_in,
                                 stats1, fake, d_key_fake)
            errD = d_loss_fn(out_r, out_f)
            return errD.astype(jnp.float32) * d.scaler.loss_scale, \
                (errD, stats2)

        (_, (errD, d_stats)), d_grads = jax.value_and_grad(
            d_forward, has_aux=True)(d_vals)
        d_new = _finish_update(d._replace(stats=d_stats), d_grads,
                               d_update, d_dtypes)

        # 3) generator step through the UPDATED discriminator (reference
        # ordering: errG after optimizerD.step())
        d_vals_new = model_vals_of(d_new)

        def g_forward(g_vals_in):
            fake2, g_stats = _run(netG, g_params, g_buffers, g_vals_in,
                                  g.stats, z, g_key)
            out_f, d_stats2 = _run(netD, d_params, d_buffers, d_vals_new,
                                   d_new.stats, fake2, d_key_gstep)
            errG = g_loss_fn(out_f)
            return errG.astype(jnp.float32) * g.scaler.loss_scale, \
                (errG, g_stats, d_stats2)

        (_, (errG, g_stats, d_stats2)), g_grads = jax.value_and_grad(
            g_forward, has_aux=True)(g_vals)
        g_new = _finish_update(g._replace(stats=g_stats), g_grads,
                               g_update, g_dtypes)
        d_new = d_new._replace(stats=d_stats2)

        return GanStepState(d_new, g_new), (errD, errG)

    init_state = GanStepState(
        d=init_step_state(d_params, d_buffers, d_dtypes, d_opt_init,
                          init_scale),
        g=init_step_state(g_params, g_buffers, g_dtypes, g_opt_init,
                          init_scale))

    # the GAN iteration dispatches through the runtime executor like
    # every other step kind: cached compile, dispatch span + counters,
    # watchdog heartbeats
    program = _executor.Program(
        "gan_train_step", (next(_GAN_TOKENS), bool(donate_state)), step_fn,
        donate_argnums=(0,) if donate_state else ())
    dispatch_no = itertools.count(1)

    def jit_step(state, real, z):
        return _executor.executor.submit(
            program, (state, real, z), step=next(dispatch_no))

    return GanTrainStep(netD, netG, optD, optG, jit_step,
                        (d_params, d_buffers), (g_params, g_buffers),
                        init_state)
