"""Fused train-step builder: the TPU-native fast path.

Where the reference's hot loop is Python driving kernels (SURVEY.md §3.2),
here the entire iteration — forward, backward, unscale + overflow check,
conditional skip, optimizer update, loss-scale update, BN running stats —
compiles into ONE XLA executable with zero host round-trips.  The stateful
facade (model/optimizer/scaler objects) is synchronized from the returned
device state, so the imperative API and the fused path are interchangeable.

This is the path ``bench.py``, the examples and DistributedDataParallel use;
``amp.scale_loss`` + ``loss.backward()`` (apex_tpu.autograd) is the
API-parity path.
"""
from __future__ import annotations

import functools
import itertools
import time
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..amp.scaler import ScalerState, update_scale_state
from ..compat import axis_size as _axis_size
from ..nn.modules import Ctx
from ..nn.parameter import Parameter
from ..observe import spans as _obs_spans
from ..observe import telemetry as _obs_telemetry
from ..observe import watchdog as _obs_watchdog

#: per-make_train_step token in the step_cache static key — two step
#: programs with identical signatures but different closures (model /
#: optimizer / loss_fn objects) must never share a cache entry
_STEP_TOKENS = itertools.count()


class StepState(NamedTuple):
    """Device-side training state for the fused step."""
    master_params: list          # fp32 masters (or the params themselves)
    model_params: list           # half copies fed to forward (may be same)
    opt_state: dict              # optimizer slots, name -> list
    scaler: ScalerState
    stats: list                  # module buffer values (BN running stats)
    step: jax.Array              # i32
    #: observe.StepTelemetry accumulator, or None (telemetry off).  None
    #: flattens to an empty subtree, so the leaf signature — and every
    #: checkpoint saved before this field existed — is unchanged when off.
    telem: Optional[object] = None


class TrainStep:
    """Built by :func:`make_train_step`; owns the compiled step and the
    object<->state synchronization."""

    def __init__(self, model, optimizer, loss_fn, step_fn, params, buffers,
                 init_state):
        self.model = model
        self.optimizer = optimizer
        self.loss_fn = loss_fn
        self._step_fn = step_fn
        self._params = params
        self._buffers = buffers
        self.state = init_state
        #: wall seconds of the first call (≈ trace + XLA compile: jit
        #: compilation is synchronous at dispatch, execution is async).
        #: Round-1 lesson: compile cost was invisible until it timed out.
        self.compile_s = None
        #: 0-based count of dispatched calls (chaos `at=` indices key on it)
        self.calls = 0
        #: resilience.BadStepGuard attached via guard.attach(step), or None
        self._guard = None
        #: the parallel.auto.Plan that built this step (parallel=), or None
        self.plan = None
        #: the PlanReport behind parallel="auto", or None
        self.plan_report = None
        #: on-device telemetry accumulation (make_train_step telemetry=)
        self._telemetry = False
        #: windows between host drains of the on-device accumulator
        self._drain_every = 1
        #: True when _step_fn submits through runtime.executor, which
        #: then owns the dispatch span + watchdog heartbeat; False for
        #: steps dispatched by other wrappers (pipeline, manual
        #: shard_map), where this facade emits them itself
        self._via_executor = False

    def __call__(self, *batch):
        from ..runtime import chaos as _chaos
        if _chaos.active():
            batch = _chaos_taint(self, batch)
        t0 = time.perf_counter() if self.compile_s is None else None
        if self._via_executor:
            self.state, loss = self._step_fn(self.state, *batch)
        else:
            with _obs_spans.span("dispatch"):
                self.state, loss = self._step_fn(self.state, *batch)
        if t0 is not None:
            self.compile_s = time.perf_counter() - t0
        self.calls += 1
        if not self._via_executor:
            # dispatch returned == the host made forward progress
            # (execution is async; a heartbeat after enqueue is exactly
            # the liveness signal the stall watchdog wants — a wedged
            # backend blocks the dispatch).  The executor path emits
            # this itself at submit time.
            _obs_watchdog.heartbeat(step=self.calls)
        if self._guard is not None:
            # the on-device skip flag apply_fused_update carried out in
            # scaler.overflow — handing the array over costs nothing; the
            # guard reads it lazily (is_ready polling)
            self._guard.observe(self.state.scaler.overflow)
        if self._telemetry and self.calls % self._drain_every == 0:
            self.drain_telemetry()
        return loss

    def drain_telemetry(self):
        """Host-sync the on-device telemetry accumulator and reset it.

        The drain lives in :func:`apex_tpu.runtime.executor.
        drain_telemetry` — the carry-drain shared by every step kind —
        and stays eager code outside jit, so the HOST-SYNC invariant
        holds and the compiled window program stays 1 compile +
        1 dispatch.  Emits a ``train.telemetry`` event and returns the
        record (None when telemetry is off or no window has completed
        since the last drain).
        """
        from ..runtime import executor as _executor
        return _executor.drain_telemetry(self)

    @property
    def last_step_skipped(self):
        """Device i32 scalar: 1 when the most recent call overflow-skipped
        (reading it as ``int(...)`` is a host sync)."""
        return self.state.scaler.overflow

    def sync_to_objects(self):
        """Write device state back into the model/scaler objects.

        The optimizer's param_groups reference the SAME Parameter objects as
        the model (make_train_step never swaps masters in), so each param
        gets its model-dtype value (half where cast, else the fp32 master);
        the fp32 masters live in ``self.state.master_params``.
        """
        st = self.state
        meta = getattr(self, "_flat_meta", None)
        if meta is not None:
            for i, (bid, j) in enumerate(meta.pos):
                half = st.model_params[bid]
                src_buf = st.master_params[bid] if half is None else half
                self._params[i].data = _row(src_buf, j, meta.shapes[i])
        else:
            for i, (p, v) in enumerate(zip(self._params, st.model_params)):
                p.data = st.master_params[i] if v is None else v
        for b, v in zip(self._buffers, st.stats):
            b.data = v
        from ..amp._amp_state import _amp_state
        if _amp_state.loss_scalers:
            _amp_state.loss_scalers[0].state = st.scaler

    def load_state(self, host_state):
        """Re-device a host checkpoint state into this step, laying each
        leaf out under its CURRENT placement (the elastic cross-plan
        restore entry; ``runtime.resilience.reshard_state`` holds the
        validation contract — typed ``CheckpointReshardError`` on a
        structural mismatch, values never touched by arithmetic)."""
        from ..runtime.resilience import reshard_state
        self.state = reshard_state(host_state, self.state)
        return self


def _chaos_taint(train_step, batch):
    """``train.step`` chaos hook: ``"nonfinite_grads"`` multiplies every
    floating batch leaf by NaN, so the scaled loss — and therefore every
    gradient — goes non-finite and the fused step's own overflow machinery
    (flag → skip → scale halving) fires exactly as it would in a real
    overflow storm.  ``"kill"``/``"fail"`` raise from the hook itself."""
    from ..runtime import chaos as _chaos

    action = _chaos.hook("train.step", step=train_step.calls)
    if action != "nonfinite_grads":
        return batch

    def taint(x):
        if hasattr(x, "dtype") and jnp.issubdtype(
                jnp.asarray(x).dtype, jnp.floating):
            return jnp.asarray(x) * jnp.asarray(float("nan"),
                                                jnp.asarray(x).dtype)
        return x
    return tuple(jax.tree_util.tree_map(taint, b) for b in batch)


def match_param_groups(optimizer, params, caller="make_train_step"):
    """Match optimizer param_groups to ``params`` by identity → per-group
    index lists.  Hyperparameters come from each param's own group; model
    params held by no group are frozen (torch semantics)."""
    id2idx = {id(p): i for i, p in enumerate(params)}
    group_idxs: list = []
    for gi, group in enumerate(optimizer.param_groups):
        idxs = []
        for p in group["params"]:
            if id(p) not in id2idx:
                raise ValueError(
                    f"{caller}: optimizer param_groups[{gi}] holds a "
                    f"parameter (shape {tuple(p.shape)}) that is not one of "
                    f"model.parameters(); the fused step requires the "
                    f"optimizer to optimize the model's own parameters")
            idxs.append(id2idx[id(p)])
        group_idxs.append(idxs)
    return group_idxs


def _gather(lst, idxs):
    return [lst[i] for i in idxs]


def _scatter(dst, idxs, new):
    for i, v in zip(idxs, new):
        dst[i] = v


def _model_dtypes(model, params, half_dtype, keep_batchnorm_fp32):
    from ..nn.modules import _BatchNorm

    bn_param_ids = set()
    if keep_batchnorm_fp32:
        for m in model.modules():
            if isinstance(m, _BatchNorm):
                for p in m._parameters.values():
                    if p is not None:
                        bn_param_ids.add(id(p))
    if half_dtype is None:
        return [p.data.dtype for p in params]
    return [jnp.float32 if id(p) in bn_param_ids else jnp.dtype(half_dtype)
            for p in params]


def apply_fused_update(sub: StepState, grads, opt_update, model_dtypes, *,
                       dynamic, init_scale, scale_window,
                       min_loss_scale, max_loss_scale, lr_schedule=None,
                       loss=None, telem_axes=()):
    """The post-gradient half of a fused step: unscale into fp32 master
    grads + overflow flag, fused optimizer update, skip-on-overflow
    (lax.select keeps it fused), model-dtype re-cast, loss-scale update.
    Returns the new sub-state with ``sub.stats`` passed through.

    bf16-style runs (static scale 1.0) skip the non-finite reduction: no
    scaling means no scaled-overflow to detect, and the extra full pass over
    every gradient costs real step time (the reference likewise early-outs
    in unscale for scale==1.0 non-dynamic, apex/amp/scaler.py:102-103).
    """
    check_overflow = dynamic or init_scale != 1.0
    flag = jnp.zeros((), jnp.int32)
    master_grads = []
    if check_overflow:
        inv = 1.0 / sub.scaler.loss_scale
    for g in grads:
        gf = g.astype(jnp.float32)
        if check_overflow:
            gf = gf * inv
            flag = jnp.maximum(flag, (~jnp.isfinite(gf)).any()
                               .astype(jnp.int32))
        master_grads.append(gf)

    step_count = sub.step + 1
    if lr_schedule is None:
        new_masters, new_slots = opt_update(
            flag, master_grads, sub.master_params, sub.opt_state, step_count)
    else:
        # schedules see the 1-based step as a traced scalar and return a
        # multiplier on each group's base lr — on-device, no recompiles
        new_masters, new_slots = opt_update(
            flag, master_grads, sub.master_params, sub.opt_state, step_count,
            lr_scale=lr_schedule(step_count))

    skip = flag > 0
    sel = functools.partial(jnp.where, skip)
    masters = [sel(o, n) for o, n in zip(sub.master_params, new_masters)]
    slots = {k: [sel(o, n) for o, n in zip(sub.opt_state[k], new_slots[k])]
             for k in new_slots}
    model_params = [
        None if jnp.dtype(d) == jnp.dtype(jnp.float32) else m.astype(d)
        for m, d in zip(masters, model_dtypes)]
    step_count = jnp.where(skip, sub.step, step_count)

    scaler_state = ScalerState(sub.scaler.loss_scale, sub.scaler.unskipped,
                               flag)
    new_scaler, _ = update_scale_state(
        scaler_state, dynamic=dynamic, scale_window=scale_window,
        min_loss_scale=min_loss_scale, max_loss_scale=max_loss_scale)
    # carry THIS step's skip flag out in the returned scaler state: the
    # fused path never reads `overflow` on entry (the flag is recomputed
    # from the gradients each step), so the slot is free to make "did the
    # step skip" observable on device — BadStepGuard consumes it without
    # adding a host sync to the step
    new_scaler = new_scaler._replace(overflow=flag)
    telem = sub.telem
    if telem is not None:
        # fold this window's observables into the donated carry — pure
        # jnp, stays inside the one compiled program, drained by
        # TrainStep.drain_telemetry from eager code
        telem = _obs_telemetry.accumulate(
            telem, loss=loss, master_grads=master_grads, flag=flag,
            loss_scale=new_scaler.loss_scale, mean_axes=telem_axes)
    return StepState(masters, model_params, slots, new_scaler, sub.stats,
                     step_count, telem)


def init_step_state(params, buffers, model_dtypes, opt_init, init_scale):
    """Initial device state for a fused step.  copy=True: .astype is a
    no-op view for already-fp32 params, and the state is donated — without
    the copy the first step would delete the live Parameter.data /
    Buffer.data arrays out from under the model."""
    from ..inference.quant import QuantTensor
    for p in params:
        if isinstance(p.data, QuantTensor):
            raise ValueError(
                "this model has int8-quantized weights "
                "(apex_tpu.inference.quantize_int8) — quantized models "
                "are inference-only; rebuild/reload the model to train")
    masters0 = [jnp.array(p.data, dtype=jnp.float32, copy=True)
                for p in params]
    return StepState(
        master_params=masters0,
        model_params=[
            None if jnp.dtype(d) == jnp.dtype(jnp.float32)
            else m.astype(d) for m, d in zip(masters0, model_dtypes)],
        opt_state=opt_init(),
        scaler=ScalerState(jnp.asarray(init_scale, jnp.float32),
                           jnp.zeros((), jnp.int32),
                           jnp.zeros((), jnp.int32)),
        stats=[jnp.array(b.data, copy=True) for b in buffers],
        step=jnp.zeros((), jnp.int32))


def model_vals_of(sub: StepState):
    """Forward-pass param values: the half copy where cast, else the fp32
    master (model_params holds None where no cast is needed — sharing the
    master buffer would double-donate under buffer donation)."""
    return [sub.master_params[i] if mp is None else mp
            for i, mp in enumerate(sub.model_params)]


def build_opt_update(optimizer, params, group_idxs,
                     caller="make_train_step"):
    """Map a fused optimizer instance to a pure update over flat lists,
    applied per group (hyperparameters are read at trace time;
    mutate-and-recompile to change them mid-training, as with any jitted
    step).  Returns ``(opt_update, opt_init)``."""
    from ..optimizers import FusedAdam, FusedLAMB, FusedNovoGrad, FusedSGD
    from .. import ops

    opt = optimizer
    if isinstance(opt, FusedSGD):
        def opt_update(flag, grads, masters, slots, step, lr_scale=1.0):
            new_p, new_m = list(masters), list(slots["momentum"])
            for group, idxs in zip(opt.param_groups, group_idxs):
                if not idxs:
                    continue
                flag, g_p, g_m = ops.multi_tensor_sgd(
                    flag, [_gather(grads, idxs), _gather(new_p, idxs),
                           _gather(new_m, idxs)],
                    group["weight_decay"], group["momentum"],
                    group["dampening"], group["lr"] * lr_scale,
                    group["nesterov"],
                    False, opt.wd_after_momentum, 1.0)
                _scatter(new_p, idxs, g_p)
                _scatter(new_m, idxs, g_m)
            return new_p, {"momentum": new_m}

        def opt_init():
            return {"momentum": [jnp.zeros(p.shape, jnp.float32)
                                 for p in params]}
    elif isinstance(opt, FusedAdam):
        def opt_update(flag, grads, masters, slots, step, lr_scale=1.0):
            new_p = list(masters)
            new_m, new_v = list(slots["m"]), list(slots["v"])
            for group, idxs in zip(opt.param_groups, group_idxs):
                if not idxs:
                    continue
                b1, b2 = group["betas"]
                _, g_p, g_m, g_v = ops.multi_tensor_adam(
                    flag, [_gather(grads, idxs), _gather(new_p, idxs),
                           _gather(new_m, idxs), _gather(new_v, idxs)],
                    group["lr"] * lr_scale, b1, b2, group["eps"], step,
                    opt.adam_w_mode, bool(group["bias_correction"]),
                    group["weight_decay"])
                _scatter(new_p, idxs, g_p)
                _scatter(new_m, idxs, g_m)
                _scatter(new_v, idxs, g_v)
            return new_p, {"m": new_m, "v": new_v}

        def opt_init():
            z = [jnp.zeros(p.shape, jnp.float32) for p in params]
            return {"m": z, "v": [jnp.zeros(p.shape, jnp.float32)
                                  for p in params]}
    elif isinstance(opt, FusedLAMB):
        def opt_update(flag, grads, masters, slots, step, lr_scale=1.0):
            new_p = list(masters)
            new_m, new_v = list(slots["m"]), list(slots["v"])
            for group, idxs in zip(opt.param_groups, group_idxs):
                if not idxs:
                    continue
                b1, b2 = group["betas"]
                # per-group global grad norm, matching the eager
                # FusedLAMB.step's per-dtype-bucket l2norm (fused_lamb.py:26)
                _, gnorm, _ = ops.multi_tensor_l2norm(
                    flag, [_gather(grads, idxs)])
                _, g_p, g_m, g_v = ops.multi_tensor_lamb(
                    flag, [_gather(grads, idxs), _gather(new_p, idxs),
                           _gather(new_m, idxs), _gather(new_v, idxs)],
                    group["lr"] * lr_scale, b1, b2, group["eps"], step,
                    bool(group["bias_correction"]), group["weight_decay"],
                    1 if group["grad_averaging"] else 0, opt.adam_w_mode,
                    gnorm, group["max_grad_norm"])
                _scatter(new_p, idxs, g_p)
                _scatter(new_m, idxs, g_m)
                _scatter(new_v, idxs, g_v)
            return new_p, {"m": new_m, "v": new_v}

        def opt_init():
            z = [jnp.zeros(p.shape, jnp.float32) for p in params]
            return {"m": z, "v": [jnp.zeros(p.shape, jnp.float32)
                                  for p in params]}
    elif isinstance(opt, FusedNovoGrad):
        def opt_update(flag, grads, masters, slots, step, lr_scale=1.0):
            new_p = list(masters)
            new_m, new_n = list(slots["m"]), list(slots["grad_norms"])
            for group, idxs in zip(opt.param_groups, group_idxs):
                if not idxs:
                    continue
                b1, b2 = group["betas"]
                norm_type = group["norm_type"]
                g_grads = _gather(grads, idxs)
                # first-step norm init (reference fused_novograd.py:158-174):
                # seed the running norm with ||g|| so the first blend is a
                # no-op, unless init_zero
                norms_in = _gather(new_n, idxs)
                if not group["init_zero"]:
                    def _local_norm(g):
                        gf = g.astype(jnp.float32)
                        return (jnp.max(jnp.abs(gf)) if norm_type == 0
                                else jnp.sqrt(jnp.sum(gf * gf)))
                    norms_in = [
                        jnp.where(step == 1, _local_norm(g), n)
                        for g, n in zip(g_grads, norms_in)]
                _, g_p, g_m, g_n = ops.multi_tensor_novograd(
                    flag, [g_grads, _gather(new_p, idxs),
                           _gather(new_m, idxs), norms_in],
                    group["lr"] * lr_scale, b1, b2, group["eps"], step,
                    bool(group["bias_correction"]), group["weight_decay"],
                    1 if group["grad_averaging"] else 0, opt.moment_mode,
                    norm_type)
                _scatter(new_p, idxs, g_p)
                _scatter(new_m, idxs, g_m)
                _scatter(new_n, idxs, g_n)
            return new_p, {"m": new_m, "grad_norms": new_n}

        def opt_init():
            return {"m": [jnp.zeros(p.shape, jnp.float32) for p in params],
                    "grad_norms": [jnp.zeros((), jnp.float32)
                                   for _ in params]}
    else:
        raise TypeError(
            f"{caller} does not support {type(opt).__name__}; "
            f"supported: FusedSGD, FusedAdam, FusedLAMB, FusedNovoGrad")
    return opt_update, opt_init


class FlatMeta(NamedTuple):
    """Layout of the shape-bucketed master/slot buffers.

    One buffer per (param group, shape, model dtype) bucket: the
    bucket's tensors STACK on a new leading axis, so each keeps its
    native TPU tiling — a truly flat 1-D buffer measurably lost 24%
    ResNet step time to 1-D→tiled relayouts (convert+reshape ~17 ms,
    BENCH round 5); leading-axis stacking keeps slices and casts
    layout-preserving and nearly free while the update still runs as
    one fused op per bucket (~2 dozen) instead of one per param
    (~161)."""
    buckets: list    # [(group_index, shape, dtype, [param indices])]
    pos: list        # per PARAM: (bucket_id, index within bucket)
    shapes: list     # per PARAM: original shape


def build_flat_meta(params, group_idxs, model_dtypes):
    buckets, pos = [], [None] * len(params)
    key2bid = {}
    for gi, idxs in enumerate(group_idxs):
        for i in idxs:
            key = (gi, tuple(params[i].data.shape),
                   jnp.dtype(model_dtypes[i]).name)
            if key not in key2bid:
                key2bid[key] = len(buckets)
                buckets.append((gi, tuple(params[i].data.shape),
                                jnp.dtype(model_dtypes[i]).name, []))
            bid = key2bid[key]
            pos[i] = (bid, len(buckets[bid][3]))
            buckets[bid][3].append(i)
    return FlatMeta(buckets, pos, [tuple(p.data.shape) for p in params])


def _row(stacked, j, shape):
    # static leading-axis slice: layout-preserving, folds into consumers
    return jax.lax.index_in_dim(stacked, j, axis=0, keepdims=False)


def flat_param_values(meta: FlatMeta, masters, model_params,
                      model_dtypes):
    """Per-param forward values: half params take a row of the
    bucket's one half-cast stack, fp32 params (BN under
    keep_batchnorm_fp32) a row of the f32 master stack."""
    out = [None] * len(meta.shapes)
    for i, (bid, j) in enumerate(meta.pos):
        src = masters[bid] if model_params[bid] is None else \
            model_params[bid]
        out[i] = _row(src, j, meta.shapes[i])
    return out


def flat_model_params(meta: FlatMeta, masters, model_dtypes):
    """Per-BUCKET half copy — one full-stack cast per bucket per step;
    None for fp32 buckets (their forward values read the master)."""
    out = []
    for bid, (gi, shape, dname, idxs) in enumerate(meta.buckets):
        d = jnp.dtype(dname)
        out.append(None if d == jnp.dtype(jnp.float32)
                   else masters[bid].astype(d))
    return out


def build_opt_update_flat(optimizer, meta: FlatMeta,
                          caller="make_train_step"):
    """Per-BUCKET stacked update: each bucket's (grad, master, slots)
    are single stacked arrays, so the multi-tensor op runs once per
    bucket (a couple dozen fused ops) with its group's hyperparams.
    Only elementwise-per-parameter optimizers are eligible — LAMB's
    trust ratio and NovoGrad's running norms are per-tensor quantities
    a stacked update would silently compute per bucket instead."""
    from ..optimizers import FusedAdam, FusedSGD
    from .. import ops

    opt = optimizer
    bucket_groups = [b[0] for b in meta.buckets]
    if isinstance(opt, FusedSGD):
        def opt_update(flag, grads, masters, slots, step, lr_scale=1.0):
            new_p, new_m = [], []
            for bid, gi in enumerate(bucket_groups):
                group = opt.param_groups[gi]
                flag, g_p, g_m = ops.multi_tensor_sgd(
                    flag, [[grads[bid]], [masters[bid]],
                           [slots["momentum"][bid]]],
                    group["weight_decay"], group["momentum"],
                    group["dampening"], group["lr"] * lr_scale,
                    group["nesterov"],
                    False, opt.wd_after_momentum, 1.0)
                new_p.append(g_p[0])
                new_m.append(g_m[0])
            return new_p, {"momentum": new_m}

        def opt_init(bucket_shapes):
            return {"momentum": [jnp.zeros(s, jnp.float32)
                                 for s in bucket_shapes]}
    elif isinstance(opt, FusedAdam):
        def opt_update(flag, grads, masters, slots, step, lr_scale=1.0):
            new_p, new_m, new_v = [], [], []
            for bid, gi in enumerate(bucket_groups):
                group = opt.param_groups[gi]
                b1, b2 = group["betas"]
                _, g_p, g_m, g_v = ops.multi_tensor_adam(
                    flag, [[grads[bid]], [masters[bid]], [slots["m"][bid]],
                           [slots["v"][bid]]],
                    group["lr"] * lr_scale, b1, b2, group["eps"], step,
                    opt.adam_w_mode, bool(group["bias_correction"]),
                    group["weight_decay"])
                new_p.append(g_p[0])
                new_m.append(g_m[0])
                new_v.append(g_v[0])
            return new_p, {"m": new_m, "v": new_v}

        def opt_init(bucket_shapes):
            return {"m": [jnp.zeros(s, jnp.float32) for s in bucket_shapes],
                    "v": [jnp.zeros(s, jnp.float32)
                          for s in bucket_shapes]}
    else:
        raise TypeError(
            f"{caller}: flat_master=True supports the elementwise "
            f"optimizers (FusedSGD, FusedAdam); {type(opt).__name__} "
            f"updates depend on per-tensor norms (LAMB trust ratio, "
            f"NovoGrad running norms) that stacked buffers would "
            f"change — use flat_master=False")
    return opt_update, opt_init


def apply_fused_update_flat(sub: StepState, grads, meta: FlatMeta,
                            opt_update, model_dtypes, *,
                            dynamic, init_scale, scale_window,
                            min_loss_scale, max_loss_scale,
                            lr_schedule=None, loss=None, telem_axes=()):
    """Stacked twin of :func:`apply_fused_update`: per-tensor grads
    stack once per shape bucket (layout-preserving leading-axis
    concat), then unscale/overflow, update, and the skip select each
    run as one full-stack op per bucket."""
    check_overflow = dynamic or init_scale != 1.0
    flag = jnp.zeros((), jnp.int32)
    flat_grads = []
    inv = 1.0 / sub.scaler.loss_scale if check_overflow else None
    for bid, (gi, shape, dname, idxs) in enumerate(meta.buckets):
        fg = jnp.stack([grads[i].astype(jnp.float32) for i in idxs])
        if check_overflow:
            fg = fg * inv
            flag = jnp.maximum(flag, (~jnp.isfinite(fg)).any()
                               .astype(jnp.int32))
        flat_grads.append(fg)

    step_count = sub.step + 1
    kw = {} if lr_schedule is None else \
        {"lr_scale": lr_schedule(step_count)}
    new_masters, new_slots = opt_update(
        flag, flat_grads, sub.master_params, sub.opt_state, step_count,
        **kw)

    skip = flag > 0
    sel = functools.partial(jnp.where, skip)
    masters = [sel(o, n) for o, n in zip(sub.master_params, new_masters)]
    slots = {k: [sel(o, n) for o, n in zip(sub.opt_state[k], new_slots[k])]
             for k in new_slots}
    step_count = jnp.where(skip, sub.step, step_count)

    scaler_state = ScalerState(sub.scaler.loss_scale, sub.scaler.unskipped,
                               flag)
    new_scaler, _ = update_scale_state(
        scaler_state, dynamic=dynamic, scale_window=scale_window,
        min_loss_scale=min_loss_scale, max_loss_scale=max_loss_scale)
    # skip-flag carry-out, as in apply_fused_update
    new_scaler = new_scaler._replace(overflow=flag)
    telem = sub.telem
    if telem is not None:
        # the stacked buckets cover every master grad exactly once, so the
        # sum-of-squares over buckets IS the global norm
        telem = _obs_telemetry.accumulate(
            telem, loss=loss, master_grads=flat_grads, flag=flag,
            loss_scale=new_scaler.loss_scale, mean_axes=telem_axes)
    return StepState(masters, flat_model_params(meta, masters, model_dtypes),
                     slots, new_scaler, sub.stats, step_count, telem)


def init_step_state_flat(params, buffers, meta: FlatMeta, model_dtypes,
                         opt_init, init_scale):
    from ..inference.quant import QuantTensor
    for p in params:
        if isinstance(p.data, QuantTensor):
            raise ValueError(
                "this model has int8-quantized weights "
                "(apex_tpu.inference.quantize_int8) — quantized models "
                "are inference-only; rebuild/reload the model to train")
    masters0 = [
        jnp.stack([jnp.asarray(params[i].data, jnp.float32)
                   for i in idxs])
        for (gi, shape, dname, idxs) in meta.buckets]
    return StepState(
        master_params=masters0,
        model_params=flat_model_params(meta, masters0, model_dtypes),
        opt_state=opt_init([m.shape for m in masters0]),
        scaler=ScalerState(jnp.asarray(init_scale, jnp.float32),
                           jnp.zeros((), jnp.int32),
                           jnp.zeros((), jnp.int32)),
        stats=[jnp.array(b.data, copy=True) for b in buffers],
        step=jnp.zeros((), jnp.int32))


def _default_zero_mesh(zero_axis):
    """Default ZeRO mesh: the ambient mesh context when one is active
    (a step built inside ``with Mesh(...):`` must not silently rebuild a
    1-D mesh over ALL ``jax.devices()`` — on a dp×tp submesh that would
    shard masters across devices the step never runs on), else a 1-D
    mesh over every device."""
    ambient = None
    try:
        from jax._src import mesh as _mesh_lib
        m = _mesh_lib.thread_resources.env.physical_mesh
        if m is not None and not m.empty:
            ambient = m
    except Exception:       # private surface moved: fall back to global
        ambient = None
    if ambient is not None:
        if zero_axis in ambient.shape:
            return ambient
        raise ValueError(
            f"zero_sharding=True inside an active mesh context whose axes "
            f"{tuple(ambient.shape)} do not include zero_axis="
            f"{zero_axis!r} — pass zero_mesh= (and zero_axis=) explicitly; "
            f"the default no longer rebuilds a 1-D mesh over all "
            f"jax.devices() when the step already runs on a submesh")
    import numpy as _np
    from jax.sharding import Mesh as _Mesh
    return _Mesh(_np.array(jax.devices()), (zero_axis,))


def make_train_step(model, optimizer, loss_fn: Callable,
                    half_dtype=None,
                    keep_batchnorm_fp32: bool = True,
                    dynamic_loss_scale: bool = True,
                    scale_window: int = 2000,
                    min_loss_scale: Optional[float] = None,
                    max_loss_scale: float = 2.0 ** 24,
                    loss_scale: float | str = "dynamic",
                    axis_name: Optional[str] = None,
                    tp_axis: Optional[str] = None,
                    gradient_predivide_factor: float = 1.0,
                    allreduce_always_fp32: bool = False,
                    donate_state="auto",
                    grad_accum_steps: int = 1,
                    accum_steps: Optional[int] = None,
                    accum_stacked: bool = False,
                    lr_schedule: Optional[Callable] = None,
                    rng_seed: int = 0,
                    zero_sharding: bool = False,
                    zero_mesh=None,
                    zero_axis: str = "data",
                    zero_stage: int = 1,
                    flat_master: bool = False,
                    parallel=None,
                    example_batch=None,
                    devices=None,
                    auto_tune: int = 0,
                    plan_options=None,
                    telemetry: bool = False,
                    drain_every: int = 1,
                    overlap="auto",
                    _plan=None,
                    _gather_prefetch_mesh=None,
                    _gather_prefetch_axis="data",
                    _gather_prefetch_sharded=True,
                    _gather_prefetch_on=False):
    """Build a fully-fused O2-style train step.

    ``loss_fn(outputs..., *batch_tail) -> scalar``: called with the model
    output.  The step signature is ``step(state, *batch) -> (state, loss)``
    where ``batch[0]`` feeds the model and the full batch feeds ``loss_fn``.

    ``accum_steps=K`` (preferred name; ``grad_accum_steps`` is the
    original spelling and stays accepted) runs the batch as K sequential
    microbatches inside the SAME compiled step (a ``lax.scan``),
    accumulating gradients in fp32 and applying one optimizer update —
    peak activation memory is that of one microbatch.  By default the
    step splits a flat ``(K*B, ...)`` batch itself; with
    ``accum_stacked=True`` it consumes pre-stacked ``(K, B, ...)``
    microbatch blocks (what ``runtime.DataPrefetcher(accum_steps=K)``
    delivers) with no reshape.  Everything that follows the window —
    optimizer update, master→half cast, dynamic-scale update, and the
    DP/TP gradient exchange — happens exactly once at the window
    boundary, and an overflow in ANY microbatch skips the whole window
    (the flag ORs across microbatches through the fp32 accumulator: a
    non-finite microbatch gradient keeps the sum non-finite).  Reported
    loss is the microbatch mean.  Batch
    elements sharing the model input's leading dim are split; anything
    else (scalars, per-step constants, custom containers) is broadcast to
    every microbatch.  The step matches the full-batch step up to
    summation order PROVIDED ``loss_fn`` computes a per-sample mean (the
    default reductions): gradients are (1/K)·Σ microbatch grads.  A
    sum-reduction or weight-normalized loss does not decompose that way —
    its accumulated gradients are 1/K of the full-batch run's, exactly as
    when a torch user accumulates ``loss / K`` manually.  (BatchNorm
    normalizes within each microbatch, as everywhere.)  Under DP the
    gradient all-reduce happens once per step, after accumulation — the
    reference's ``delay_unscale=True`` grad-accumulation pattern
    (docs/advanced.md), fused.

    When ``axis_name`` is given the step is meant to run under
    ``shard_map``/``pjit`` over that mesh axis: gradients are psum-averaged
    with the reference DDP's knobs honored (``gradient_predivide_factor``
    splits the averaging before/after the all-reduce,
    apex/parallel/distributed.py:445-454; ``allreduce_always_fp32`` casts
    grads to fp32 for the collective, :417-421).

    ``tp_axis``: the model was built with Megatron tensor parallelism over
    this mesh axis (``tp_axis=`` on the GPT/BERT families).  Each TP
    device's gradient for a sharded parameter is block-sparse — only its
    own head/feature block is nonzero — so those gradients are psum'd
    (NOT averaged: the blocks are disjoint, the psum assembles the full
    gradient) over the axis, keeping the replicated full parameters and
    optimizer state consistent across TP devices.  The model must expose
    ``tp_sharded_params()``; all other gradients are already identical
    across the axis (the row-parallel psums replicate every activation
    the replicated parameters touch) and are left alone.  Composes with
    ``axis_name`` for DP×TP meshes — batch sharded over ``axis_name``,
    replicated over ``tp_axis``.

    ``flat_master=True``: the reference amp_C design
    (csrc/multi_tensor_apply.cuh chunks many tensors into one kernel
    sweep), TPU-style — fp32 masters and optimizer slots live STACKED
    per (param group, shape, dtype) bucket, the per-step unscale +
    update + skip select run as one fused op per bucket (~2 dozen)
    instead of one per param (~161), and the forward reads
    layout-preserving leading-axis rows.  Supported for the
    elementwise optimizers (FusedSGD, FusedAdam); FusedLAMB and
    FusedNovoGrad have per-TENSOR norm semantics (trust ratio /
    per-tensor running norms) that a stacked update would silently
    change, so they refuse.  Composes with axis_name/tp_axis (grad
    collectives are per-tensor, pre-stack) and grad_accum; excludes
    zero_sharding (its per-param shardings are the point there).

    MEASURED VERDICT (v5e, unledgered run, round 5): a NEGATIVE result,
    kept as the reference design's receipt.  ResNet-50 b128: 2256
    img/s stacked vs 2355 per-tensor (a truly flat 1-D layout was far
    worse, 1806 — the 1-D→tiled relayouts cost ~17 ms/step).  The
    profile shows why there was nothing to win: the presumed
    "optimizer adds" tail (~4.5 ms op:add) is identical in every arm —
    it is the residual-join gradient adds of the conv backward, not
    optimizer work — and XLA already runs the per-tensor update well.
    Default stays per-tensor; ``bench.py --flat-optim`` re-measures.

    ``zero_sharding=True``: ZeRO sharding — fp32 masters and optimizer
    slots shard over ``zero_axis`` of ``zero_mesh`` (default: a 1-D mesh
    over all devices) and XLA's GSPMD partitioner derives the
    reduce-scatter (gradients into master shards) / all-gather (updated
    masters back out) pair itself.  Returns a
    :class:`~apex_tpu.parallel.zero.ZeroTrainStep` (same calling
    surface: ``step(x, y) -> loss``, ``.state``, ``.sync_to_objects()``).
    Data parallelism is implicit — the batch shards over the axis in the
    global-view program — so ``axis_name`` must not also be given.
    ``zero_stage`` picks the scope: 1 (default) keeps the half model
    copies replicated (the win is optimizer+master memory, ~1/n per
    shardable tensor); 3 shards the half copies too (FSDP-style: each
    parameter is all-gathered just ahead of use and never stored whole —
    activation-sized gather traffic traded for O(P/n) parameter
    residency).  There is no stage 2 switch: the fused step holds no
    persistent gradient buffer — gradients are intermediates of the one
    jitted program and already land reduce-scattered into master shards.
    ``zero_stage=0`` keeps the whole state replicated and only shards the
    batch — pure GSPMD data parallelism through the same wrapper (what a
    ``parallel.auto`` plan with ``dp>1, zero=0`` threads).

    ``parallel``: ``"auto"`` or a :class:`apex_tpu.parallel.auto.Plan` —
    the analytical parallelism planner picks (or the given plan fixes)
    dp × sp × tp, ZeRO stage, accumulation K, and threads exactly the
    knobs above; ``parallel="auto"`` needs ``example_batch=`` (one global
    batch of arrays or ShapeDtypeStructs) so the planner knows the batch/
    sequence geometry, and ``auto_tune=k`` compiles and times the top-k
    predicted plans and re-ranks by measurement.  See
    ``docs/auto_parallel.md``.

    ``telemetry=True``: accumulate per-window loss, global master-grad
    L2 norm, loss scale, and overflow count ON DEVICE inside the same
    compiled program (5 extra scalar slots in the donated carry — the
    PR 3 skip-flag discipline), drained to host by
    ``TrainStep.drain_telemetry`` every ``drain_every`` windows from
    eager code.  The window program stays 1 compile + 1 dispatch; the
    drain is the one (amortized) host sync.  See ``docs/observability.md``.
    Works on every kind: under ``axis_name``/``tp_axis`` the accumulator
    pmeans the per-shard loss over the batch axes inside the step (the
    exchanged gradients are already replicated, so the grad norm needs
    no extra collective); under ``zero_sharding`` the global-view
    program carries the scalars replicated; ``parallel=`` threads it
    through whichever kind the plan picks.

    ``overlap``: True/False/"auto" — ZeRO all-gather prefetch inside the
    scanned accumulation window (the replicated parameter view for
    microbatch i+1 is issued under microbatch i's compute, the
    weight-update-sharding overlap of arXiv:2004.13336).  "auto" defers
    to :func:`apex_tpu.runtime.executor.overlap_enabled` — on for
    backends with async collectives, off on cpu, where XLA runs
    collectives synchronously (forcing it on there is bitwise-identical,
    just not faster; the parity tests do exactly that).  Only meaningful
    with ``zero_sharding`` (stage 1/3) and ``accum_steps > 1``.

    ``donate_state``: "auto" (default) follows the executor's
    :class:`~apex_tpu.runtime.executor.DonationPolicy` — donate on
    tpu/gpu (in-place buffer reuse), skip on cpu, where XLA degrades
    donation to defensive copies (measured 2x step time).  Pass
    True/False to force.
    """
    from ..runtime import executor as _executor

    donate_state = _executor.donation.resolve(donate_state)
    if telemetry and drain_every < 1:
        raise ValueError(f"drain_every must be >= 1, got {drain_every}")
    if parallel is not None:
        if axis_name is not None or tp_axis is not None or zero_sharding:
            raise ValueError(
                "parallel= owns the parallelism knobs — do not also pass "
                "axis_name / tp_axis / zero_sharding (the plan threads "
                "them; spell the config fully by hand instead if you "
                "want manual control)")
        if accum_steps is not None or grad_accum_steps != 1:
            raise ValueError(
                "parallel= owns gradient accumulation — the plan's K is "
                "threaded as accum_steps; drop accum_steps/"
                "grad_accum_steps")
        from ..parallel import auto as _auto
        return _auto.build_planned_step(
            model, optimizer, loss_fn, parallel,
            example_batch=example_batch, devices=devices,
            auto_tune=auto_tune, plan_options=plan_options,
            half_dtype=half_dtype,
            keep_batchnorm_fp32=keep_batchnorm_fp32,
            dynamic_loss_scale=dynamic_loss_scale,
            scale_window=scale_window, min_loss_scale=min_loss_scale,
            max_loss_scale=max_loss_scale, loss_scale=loss_scale,
            gradient_predivide_factor=gradient_predivide_factor,
            allreduce_always_fp32=allreduce_always_fp32,
            donate_state=donate_state, accum_stacked=accum_stacked,
            lr_schedule=lr_schedule, rng_seed=rng_seed,
            zero_axis=zero_axis, flat_master=flat_master,
            telemetry=telemetry, drain_every=drain_every,
            overlap=overlap)
    if accum_steps is not None:
        if grad_accum_steps not in (1, accum_steps):
            raise ValueError(
                f"accum_steps={accum_steps} conflicts with "
                f"grad_accum_steps={grad_accum_steps} — they are the same "
                f"knob (accum_steps is the preferred spelling); pass one")
        grad_accum_steps = int(accum_steps)
    if accum_stacked and grad_accum_steps == 1:
        raise ValueError(
            "accum_stacked=True requires accum_steps > 1 — stacked "
            "(K, B, ...) blocks only exist under accumulation")
    if flat_master and zero_sharding:
        raise ValueError(
            "flat_master=True excludes zero_sharding: ZeRO's win is "
            "per-parameter sharding of exactly the buffers flat_master "
            "concatenates")
    if zero_sharding:
        if zero_stage not in (0, 1, 3):
            raise ValueError(
                f"zero_stage must be 1 (optimizer-state sharding), 3 "
                f"(+ parameter sharding), or 0 (replicated state — pure "
                f"GSPMD data parallelism); got {zero_stage!r}.  Stage 2 "
                f"has no separate switch: the fused step never holds a "
                f"persistent gradient buffer, so sharded masters already "
                f"imply reduce-scattered gradients")
        if axis_name is not None or tp_axis is not None:
            raise ValueError(
                "zero_sharding=True excludes axis_name/tp_axis — ZeRO "
                "data parallelism is implicit in the global-view jitted "
                "program (no shard_map/psum); TP's explicit mesh axes "
                "belong to the shard_map path")
        from ..parallel.zero import ZeroTrainStep
        if zero_mesh is None:
            zero_mesh = _default_zero_mesh(zero_axis)
        elif zero_axis not in zero_mesh.shape:
            raise ValueError(
                f"zero_axis {zero_axis!r} is not an axis of zero_mesh "
                f"(axes: {tuple(zero_mesh.shape)})")
        # ZeRO all-gather prefetch: resolved here (the one place that
        # knows mesh + stage + K) and threaded into the recursive base
        # build.  The base always gathers the replicated parameter view
        # explicitly per microbatch; the executor's overlap knob only
        # moves where the gather is issued (inline at use vs pipelined
        # one iteration early through the scan carry), so overlap on/off
        # is bitwise-identical.  Stage 0 keeps everything replicated —
        # there is no gather to prefetch.
        prefetch_mesh = zero_mesh if (
            zero_stage in (1, 3) and grad_accum_steps > 1) else None
        base = make_train_step(
            model, optimizer, loss_fn, half_dtype=half_dtype,
            keep_batchnorm_fp32=keep_batchnorm_fp32,
            dynamic_loss_scale=dynamic_loss_scale,
            scale_window=scale_window, min_loss_scale=min_loss_scale,
            max_loss_scale=max_loss_scale, loss_scale=loss_scale,
            donate_state=False,
            grad_accum_steps=grad_accum_steps, accum_stacked=accum_stacked,
            lr_schedule=lr_schedule,
            rng_seed=rng_seed,
            telemetry=telemetry, drain_every=drain_every,
            _gather_prefetch_mesh=prefetch_mesh,
            _gather_prefetch_axis=zero_axis,
            # the model-consumed values travel sharded when they ARE the
            # sharded buffers (stage 3 copies, or the masters themselves
            # when half_dtype is None); stage-1 half copies replicate
            _gather_prefetch_sharded=(zero_stage == 3
                                      or half_dtype is None),
            _gather_prefetch_on=_executor.overlap_enabled("gather",
                                                          overlap))
        return ZeroTrainStep(base, zero_mesh, zero_axis,
                             donate=donate_state,
                             stage=zero_stage, plan=_plan)
    params = [p for p in model.parameters() if p is not None]
    buffers = [b for b in model.buffers()]
    group_idxs = match_param_groups(optimizer, params)
    model_dtypes = _model_dtypes(model, params, half_dtype,
                                 keep_batchnorm_fp32)
    flat_meta = None
    if flat_master:
        grouped = {i for idxs in group_idxs for i in idxs}
        if len(grouped) != len(params):
            raise ValueError(
                "flat_master=True requires every model parameter to be "
                "in an optimizer param_group (frozen params have no "
                "slot in the flat master buffers)")
        flat_meta = build_flat_meta(params, group_idxs, model_dtypes)
        opt_update, opt_init = build_opt_update_flat(optimizer, flat_meta)
    else:
        opt_update, opt_init = build_opt_update(optimizer, params,
                                                group_idxs)

    dynamic = loss_scale == "dynamic"
    init_scale = (min(max_loss_scale, 2.0 ** 16) if dynamic
                  else float(loss_scale))

    if grad_accum_steps < 1:
        raise ValueError(f"grad_accum_steps must be >= 1, "
                         f"got {grad_accum_steps}")

    tp_ids = frozenset()
    if tp_axis is not None:
        getter = getattr(model, "tp_sharded_params", None)
        if getter is None:
            raise ValueError(
                "tp_axis given but the model has no tp_sharded_params() — "
                "build the model with its tp_axis= option (models/gpt.py, "
                "models/bert.py) so the step knows which gradients are "
                "block-sparse")
        tp_ids = frozenset(id(p) for p in getter())

    # telemetry loss reduction: under shard_map the per-device loss is
    # the local shard mean, so the accumulator pmeans it over the batch
    # axes (the exchanged gradients are already replicated across every
    # axis — the grad norm needs no extra collective)
    telem_axes = ()
    if telemetry and axis_name is not None:
        telem_axes = (tuple(axis_name)
                      if isinstance(axis_name, (tuple, list))
                      else (axis_name,))

    def step_fn(state: StepState, *batch):
        model_vals = (flat_param_values(flat_meta, state.master_params,
                                        state.model_params, model_dtypes)
                      if flat_master else model_vals_of(state))

        prefetch = None
        prefetch_on = False
        if _gather_prefetch_mesh is not None and grad_accum_steps > 1:
            # ZeRO gather prefetch (executor overlap knob): the scanned
            # window consumes an EXPLICIT replicated view of the
            # (sharded) parameters each microbatch.  With the knob off
            # the gather is issued inline at the point of use; with it
            # on the view travels in the scan carry, gathered one
            # iteration EARLIER — the all-gather overlaps compute
            # instead of stalling the forward.  Both arms compile the
            # same math DAG (gather → forward → backward →
            # reduce-scattered grads); only the issue slot moves, so
            # overlap on/off is bitwise-identical — the parity the
            # executor tests pin by forcing the knob on under cpu.
            rep = jax.sharding.NamedSharding(
                _gather_prefetch_mesh, jax.sharding.PartitionSpec())
            _n_ax = _gather_prefetch_mesh.shape[_gather_prefetch_axis]
            _shd = jax.sharding.NamedSharding(
                _gather_prefetch_mesh,
                jax.sharding.PartitionSpec(_gather_prefetch_axis))
            prefetch_on = bool(_gather_prefetch_on)

            def prefetch(vals):
                return [jax.lax.with_sharding_constraint(v, rep)
                        for v in vals]

            def reshard_grads(grads):
                # pin each microbatch gradient back to the consumed
                # buffer's OWN zero sharding (dim-0 where divisible, the
                # zero_state_sharding rule): the backward of the gathered
                # view stays a reduce-scatter into a sharded
                # accumulator, not an all-reduce into a replicated one —
                # deterministic reduction order on both arms and no
                # full-gradient replica (the ZeRO memory win)
                if not _gather_prefetch_sharded:
                    return grads
                return [jax.lax.with_sharding_constraint(
                            g, _shd if (getattr(g, "ndim", 0) >= 1
                                        and g.shape[0] % _n_ax == 0)
                            else rep)
                        for g in grads]

        def forward(model_vals_in, stats_in, mb_idx, *b):
            env = {id(p): v for p, v in zip(params, model_vals_in)}
            stats_env = {id(bf): v for bf, v in zip(buffers, stats_in)}
            stats_out = {}
            # per-step dropout randomness, derived from the step counter so
            # the state shape stays fixed (and steps are reproducible);
            # under DP also fold in the replica index so shards draw
            # independent masks (matching per-device RNG in the reference);
            # under accumulation fold in the microbatch index likewise
            key = jax.random.fold_in(jax.random.PRNGKey(rng_seed), state.step)
            if axis_name is not None:
                # fold each mesh axis EXCEPT the model's own sp_axis:
                # the SP model families fold that one themselves
                # (fold_shard_into_key), stashing the pre-fold key as
                # Ctx.shared_key — the replicated seed ring-attention
                # dropout hashes for its cross-shard-consistent mask.
                # Folding sp here too would leave no sp-replicated key
                # anywhere in the step.
                sp = getattr(model, "sp_axis", None)
                axes = (axis_name if isinstance(axis_name, (tuple, list))
                        else (axis_name,))
                for ax in axes:
                    if ax != sp:
                        key = jax.random.fold_in(key,
                                                 jax.lax.axis_index(ax))
            if grad_accum_steps > 1:
                key = jax.random.fold_in(key, mb_idx)
            ctx = Ctx(env={**env, **stats_env}, stats_out=stats_out,
                      training=True, key=key)
            x = b[0]
            if half_dtype is not None:
                # O2 input cast (reference patches model.forward to cast
                # incoming data, _initialize.py:194-201); tree-mapped so
                # multi-input models (tuples/dicts of arrays, e.g. a
                # seq2seq's (src, tgt) pair) cast every floating leaf
                from ..amp.policy import _cast_tree
                x = _cast_tree(x, jnp.dtype(half_dtype))
            out = model.forward(ctx, x)
            loss = loss_fn(out, *b[1:])
            # auxiliary objectives modules recorded during forward (e.g.
            # the Switch-MoE load-balancing loss, models/gpt.py): part of
            # the optimized (and reported) loss, scaled with it
            if ctx.aux_losses:
                loss = loss + sum(ctx.aux_losses)
            new_stats = [stats_out.get(id(bf), sv)
                         for bf, sv in zip(buffers, stats_in)]
            return loss.astype(jnp.float32) * state.scaler.loss_scale, \
                (loss, new_stats)

        if grad_accum_steps == 1:
            (_, (loss, new_stats)), grads = jax.value_and_grad(
                forward, has_aux=True)(
                    model_vals, list(state.stats), jnp.zeros((), jnp.int32),
                    *batch)
        else:
            def split(b):
                def leaf(a):
                    n = a.shape[0]
                    if accum_stacked:
                        # (K, B, ...) blocks from the data pipeline: the
                        # microbatch axis already leads, scan consumes it
                        if n != grad_accum_steps:
                            raise ValueError(
                                f"accum_stacked=True with accum_steps="
                                f"{grad_accum_steps}: batch leading dim "
                                f"{n} is not the microbatch count — "
                                f"expected (K, B, ...) stacked blocks")
                        return a
                    if n % grad_accum_steps:
                        raise ValueError(
                            f"grad_accum_steps={grad_accum_steps}: batch "
                            f"leading dim {n} is not divisible "
                            f"into microbatches")
                    return a.reshape(
                        (grad_accum_steps, n // grad_accum_steps)
                        + a.shape[1:])
                return jax.tree.map(leaf, b)

            leaves0 = [a for a in jax.tree.leaves(batch[0])
                       if getattr(a, "ndim", 0) >= 1]
            if not leaves0:
                raise ValueError(
                    f"grad_accum_steps={grad_accum_steps}: the model input "
                    f"(batch[0]) has no leading batch dimension to split")
            n0 = leaves0[0].shape[0]

            def splittable(b):
                leaves = jax.tree.leaves(b)
                return bool(leaves) and all(
                    getattr(a, "ndim", 0) >= 1 and a.shape[0] == n0
                    for a in leaves)

            # elements (pytrees) whose every leaf shares the model
            # input's batch dim split into microbatches; anything else
            # (scalars, per-step constants) is broadcast
            splits = [i == 0 or splittable(b)
                      for i, b in enumerate(batch)]
            micro = tuple(split(b) for b, s in zip(batch, splits) if s)

            def micro_step(carry, mb):
                if prefetch is not None and prefetch_on:
                    acc, stats_in, loss_sum, i, vals = carry
                elif prefetch is not None:
                    # overlap off: same explicit gather, issued inline
                    # at the point of use — stalls the forward, but the
                    # math DAG is identical to the pipelined arm
                    acc, stats_in, loss_sum, i = carry
                    vals = prefetch(model_vals)
                else:
                    acc, stats_in, loss_sum, i = carry
                    vals = model_vals
                mb_it = iter(mb)
                full = tuple(next(mb_it) if s else b
                             for b, s in zip(batch, splits))
                (_, (l, ns)), g = jax.value_and_grad(
                    forward, has_aux=True)(vals, stats_in, i, *full)
                if prefetch is not None:
                    g = reshard_grads(g)
                if prefetch is not None and prefetch_on:
                    # issue the gather for microbatch i+1's view NOW,
                    # pinned after this microbatch's grads by the
                    # barrier (no CSE with the view just consumed, no
                    # hoist out of the scan) — the async collective
                    # overlaps the accumulate below and the next
                    # iteration's early compute
                    next_vals, g = jax.lax.optimization_barrier(
                        (prefetch(model_vals), g))
                acc = [a + gi.astype(jnp.float32)
                       for a, gi in zip(acc, g)]
                out = (acc, ns, loss_sum + l.astype(jnp.float32), i + 1)
                if prefetch is not None and prefetch_on:
                    out = out + (next_vals,)
                return out, None

            carry0 = ([jnp.zeros(v.shape, jnp.float32)
                       for v in model_vals],
                      list(state.stats),
                      jnp.zeros((), jnp.float32),
                      jnp.zeros((), jnp.int32))
            if prefetch is not None and prefetch_on:
                # prologue gather: microbatch 0's view rides in the
                # initial carry
                carry0 = carry0 + (prefetch(model_vals),)
            final_carry, _ = jax.lax.scan(micro_step, carry0, micro)
            acc, new_stats, loss_sum = final_carry[:3]
            grads = [a / grad_accum_steps for a in acc]
            loss = loss_sum / grad_accum_steps

        # DP gradient exchange (psum over the mapped axis), with DDP knobs
        if axis_name is not None:
            n = _axis_size(axis_name)
            pre = gradient_predivide_factor
            post = n / gradient_predivide_factor

            def exchange(g):
                gc = g.astype(jnp.float32) if allreduce_always_fp32 else g
                gc = gc / pre if pre != 1.0 else gc
                gc = jax.lax.psum(gc, axis_name)
                gc = gc / post
                return gc.astype(g.dtype) if allreduce_always_fp32 else gc
            grads = [exchange(g) for g in grads]

        # TP gradient assembly: sharded params' grads are block-sparse per
        # device (disjoint blocks), psum = the full gradient; everything
        # else is already replicated across the axis
        if tp_axis is not None:
            grads = [jax.lax.psum(g, tp_axis) if id(p) in tp_ids else g
                     for p, g in zip(params, grads)]

        if flat_master:
            new_state = apply_fused_update_flat(
                state._replace(stats=new_stats), grads, flat_meta,
                opt_update, model_dtypes,
                dynamic=dynamic, init_scale=init_scale,
                scale_window=scale_window, min_loss_scale=min_loss_scale,
                max_loss_scale=max_loss_scale, lr_schedule=lr_schedule,
                loss=loss, telem_axes=telem_axes)
        else:
            new_state = apply_fused_update(
                state._replace(stats=new_stats), grads, opt_update,
                model_dtypes,
                dynamic=dynamic, init_scale=init_scale,
                scale_window=scale_window, min_loss_scale=min_loss_scale,
                max_loss_scale=max_loss_scale, lr_schedule=lr_schedule,
                loss=loss, telem_axes=telem_axes)
        return new_state, loss

    if flat_master:
        init_state = init_step_state_flat(params, buffers, flat_meta,
                                          model_dtypes, opt_init,
                                          init_scale)
    else:
        init_state = init_step_state(params, buffers, model_dtypes,
                                     opt_init, init_scale)
    if telemetry:
        init_state = init_state._replace(
            telem=_obs_telemetry.init_telemetry())

    via_executor = axis_name is None and tp_axis is None
    if via_executor:
        # submit through the runtime executor (which compiles via the
        # step-program cache): the compiled window program is keyed on
        # (per-builder token, K, stacking, donation) plus the argument
        # signature, so step_cache.stats() pins exactly 1 compile and
        # 1 dispatch per accumulation window — K is part of the STATIC
        # key (a K=4 and a K=16 window are different executables), and
        # the donated state means the scan's fp32 gradient accumulator
        # and the carried masters/slots update in place across windows
        from ..runtime import step_cache as _step_cache

        token = next(_STEP_TOKENS)
        # the plan (when this step was built by parallel.auto) is part of
        # the STATIC key: compiled executables stay per-plan observables
        static_key = (token, grad_accum_steps, accum_stacked,
                      bool(donate_state), bool(telemetry),
                      _step_cache.static_plan_key(_plan))
        program = _executor.Program(
            "train_step", static_key, step_fn,
            donate_argnums=(0,) if donate_state else ())
        dispatch_no = itertools.count(1)

        def jit_step(state, *batch):
            return _executor.executor.submit(
                program, (state,) + batch, step=next(dispatch_no))
    else:
        jit_step = step_fn  # caller wraps in shard_map/pjit

    ts = TrainStep(model, optimizer, loss_fn, jit_step, params, buffers,
                   init_state)
    ts._via_executor = via_executor
    # the un-jitted step for wrappers that jit with their own shardings /
    # donation (parallel/zero.py)
    ts._raw_step_fn = step_fn
    ts._donate_state = donate_state and axis_name is None and tp_axis is None
    ts._flat_meta = flat_meta
    ts._flat_dtypes = model_dtypes
    ts._telemetry = bool(telemetry)
    ts._drain_every = int(drain_every)
    return ts
