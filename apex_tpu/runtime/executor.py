"""One-runtime executor: the single dispatch choke point.

PR 1 (the eager optimizer surface) and PR 3 (the fused train step) each
grew their own route into the step-program cache — duplicated donation
policy, dispatch counting, span/heartbeat plumbing, and carry handling.
This module collapses both onto one :class:`Executor`: every compiled
step program in the library — the four ``optimizers/fused_*`` +
``contrib/optimizers`` eager routes, the amp unscale / axpby /
master→model programs, the fused ``train_step``, the GSPMD
``zero_train_step``, and the planner's shard_map dispatch — is described
by a :class:`Program` and submitted here.  The executor owns:

* **compilation** — ``jax.jit`` is called in exactly one place
  (:meth:`Executor._jit`); programs are cached through
  :class:`~apex_tpu.runtime.step_cache.StepCache`, so ``stats()`` keeps
  pinning 1 compile + 1 dispatch per window on every surface (the
  EXEC-BYPASS lint rule enforces that no other module dispatches);
* **donation policy** — :class:`DonationPolicy` is the one place the
  True/False/"auto" buffer-donation decision lives (the copies that
  used to sit in step_cache, training/step.py and the amp handle are
  delegates now);
* **observability** — dispatch spans and stall-watchdog heartbeats are
  emitted here, uniformly for the fused and eager kinds;
* **overlap scheduling** — the knobs for ZeRO all-gather prefetch
  (:func:`overlap_enabled`, consumed by the fused step's scanned
  window) and async H2D double-buffering (:meth:`Executor.drive`,
  fused with :class:`~apex_tpu.runtime.data.DataPrefetcher`).

See ``docs/executor.md`` for the contract and the migration table from
the old per-surface ``step_cache`` call sites.
"""
from __future__ import annotations

import contextlib
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..observe import registry as _obs
from ..observe import spans as _spans
from ..observe import telemetry as _obs_telemetry
from ..observe import watchdog as _obs_watchdog
from . import step_cache as _sc

_f32 = jnp.float32

#: program kinds that are whole-training-window dispatches: these always
#: get a ``span("dispatch")`` and a watchdog heartbeat.  Eager kinds
#: (optimizer/amp programs) do not span — the eager hot path is
#: microbenchmarked and a per-step span event is a measurable fraction
#: of a small fused step — and never heartbeat (many eager dispatches
#: compose into one logical step; the *step* is the liveness unit).
TRAIN_KINDS = frozenset({"train_step", "zero_train_step",
                         "gan_train_step"})

#: serving-loop kinds (apex_tpu.serve): like train kinds they are the
#: unit of forward progress — every tick spans and heartbeats, so the
#: stall watchdog guards the decode loop the same way it guards the
#: train loop.  Unlike eager kinds there is no microbenchmarked
#: hot path concern: a serve dispatch covers a whole batched tick
#: (``block_copy``, admission's copy-on-write fork, rides in a tick).
SERVE_KINDS = frozenset({"prefill_step", "decode_step",
                         "draft_prefill_step", "spec_verify_step",
                         "block_copy"})

#: rollout-loop kinds (apex_tpu.rollout): the generate-then-train
#: runtime's own dispatches.  ``weight_publish`` is the one fused
#: train→serve cast (masters cast once to the serve dtype in a single
#: dispatch); like train/serve kinds it spans and heartbeats — a wedged
#: publish stalls the whole loop, so the watchdog must see it.
ROLLOUT_KINDS = frozenset({"weight_publish"})

_UNSET = object()


class DonationPolicy:
    """The one buffer-donation decision (satellite of the one-runtime
    refactor: this policy used to be re-derived in step_cache,
    training/step.py and the amp handle).

    ``"auto"`` donates on backends with real input→output buffer
    aliasing (tpu/gpu) and skips donation on cpu, where XLA accepts
    ``donate_argnums`` but degrades it to defensive copies (measured 2×
    eager FusedAdam step time at 10M params).  The resolved flag is
    part of every program cache key.
    """

    def __init__(self, mode="auto"):
        self._mode = mode

    @property
    def mode(self):
        return self._mode

    def set(self, mode) -> None:
        if mode not in (True, False, "auto"):
            raise ValueError(f"donation mode must be True/False/'auto', "
                             f"got {mode!r}")
        self._mode = mode

    @property
    def enabled(self) -> bool:
        """The policy resolved against the current default backend."""
        return self.resolve(self._mode)

    def resolve(self, request) -> bool:
        """Resolve a per-call request (True/False/"auto") to a bool;
        "auto" defers to the process-wide policy."""
        if request == "auto":
            if self._mode == "auto":
                return jax.default_backend() not in ("cpu",)
            request = self._mode
        return bool(request)


#: process-global donation policy (``step_cache.set_donation`` /
#: ``donation_enabled`` are thin delegates onto this object)
donation = DonationPolicy()


# ---------------------------------------------------------------------------
# Overlap policy: ZeRO all-gather prefetch + async H2D double-buffering
# ---------------------------------------------------------------------------

#: True/False/"auto" per overlap dimension.  "auto" enables overlap on
#: backends with async collectives / transfers worth hiding (tpu/gpu)
#: and disables it on cpu, where XLA:CPU runs collectives synchronously
#: — the schedule transformation is semantically a no-op there (the
#: bitwise-parity tests force it on to prove exactly that).
_OVERLAP = {"gather": "auto", "h2d": "auto"}


def set_overlap(gather=None, h2d=None) -> None:
    """Set the executor overlap knobs; each accepts True/False/"auto"
    (None leaves the knob unchanged)."""
    for name, mode in (("gather", gather), ("h2d", h2d)):
        if mode is None:
            continue
        if mode not in (True, False, "auto"):
            raise ValueError(f"overlap {name} mode must be "
                             f"True/False/'auto', got {mode!r}")
        _OVERLAP[name] = mode


def overlap_enabled(which: str, override=None) -> bool:
    """Resolve an overlap knob ("gather" or "h2d") to a bool; a
    per-step ``override`` of True/False wins, None/"auto" defers to the
    process-wide knob."""
    mode = _OVERLAP[which] if override in (None, "auto") else override
    if mode == "auto":
        return jax.default_backend() not in ("cpu",)
    return bool(mode)


# ---------------------------------------------------------------------------
# Measured H2D bandwidth: an EWMA over real device_put transfers
# ---------------------------------------------------------------------------

#: {"bw": bytes/s EWMA or None, "n": samples}.  The data path
#: (runtime.data) feeds it from timed device_put calls; the planner's
#: offload term prices host traffic against it, falling back to the
#: ChipSpec.h2d_bw prior until a real transfer has been observed.
_H2D_EWMA = {"bw": None, "n": 0}

#: ignore sub-64KiB transfers — latency-dominated, not bandwidth
_H2D_MIN_BYTES = 1 << 16


def note_h2d(nbytes: int, seconds: float) -> None:
    """Record one host-to-device transfer (bytes, wall seconds) into
    the bandwidth EWMA.  Tiny or instant transfers are ignored."""
    if nbytes < _H2D_MIN_BYTES or seconds <= 0:
        return
    bw = nbytes / seconds
    prev = _H2D_EWMA["bw"]
    _H2D_EWMA["bw"] = bw if prev is None else 0.8 * prev + 0.2 * bw
    _H2D_EWMA["n"] += 1
    _obs.gauge("executor.h2d_bw").set(_H2D_EWMA["bw"])


def measured_h2d_bw() -> Optional[float]:
    """The measured H2D bandwidth (bytes/s EWMA) or None before any
    real transfer has been timed."""
    return _H2D_EWMA["bw"]


def reset_h2d_bw() -> None:
    """Forget measured H2D bandwidth (tests)."""
    _H2D_EWMA["bw"] = None
    _H2D_EWMA["n"] = 0


#: the cluster membership epoch this process last agreed to (None
#: outside a cluster run).  Dispatch spans carry it so a trace mixing
#: pre- and post-reshard steps attributes each dispatch to the
#: membership view it ran under (apex_tpu.cluster sets it on recover).
_CLUSTER_EPOCH: Optional[int] = None


def set_cluster_epoch(epoch: Optional[int]) -> None:
    """Tag subsequent dispatch spans with the cluster membership epoch
    (None clears the tag)."""
    global _CLUSTER_EPOCH
    _CLUSTER_EPOCH = None if epoch is None else int(epoch)


def cluster_epoch() -> Optional[int]:
    """The membership epoch dispatches are currently tagged with."""
    return _CLUSTER_EPOCH


# ---------------------------------------------------------------------------
# Program descriptor
# ---------------------------------------------------------------------------


class Program:
    """Everything the executor needs to compile and dispatch one step
    program: the raw Python function plus its jit options.  Call sites
    never call ``jax.jit`` themselves (EXEC-BYPASS) — they describe the
    program and :meth:`Executor.submit` it.

    ``static_key`` must be hashable and capture every Python-level value
    ``fn`` closes over (the argument signature completes the cache key);
    ``wrap`` is an optional transform applied before jit (the planner's
    shard_map); ``in_shardings``/``out_shardings`` are forwarded to
    ``jax.jit`` only when given (the GSPMD ZeRO window).
    """

    __slots__ = ("kind", "static_key", "fn", "donate_argnums",
                 "in_shardings", "out_shardings", "wrap", "_jitted")

    def __init__(self, kind: str, static_key, fn: Callable, *,
                 donate_argnums: Tuple[int, ...] = (),
                 in_shardings=_UNSET, out_shardings=_UNSET,
                 wrap: Optional[Callable] = None):
        self.kind = kind
        self.static_key = static_key
        self.fn = fn
        self.donate_argnums = tuple(donate_argnums)
        self.in_shardings = in_shardings
        self.out_shardings = out_shardings
        self.wrap = wrap
        self._jitted = None


class Executor:
    """The dispatch choke point.  Stateless beyond its cache handle —
    the process-global :data:`executor` is the one instance the library
    routes through."""

    def __init__(self, cache: Optional[_sc.StepCache] = None):
        self._cache = cache if cache is not None else _sc.step_cache

    @property
    def cache(self) -> _sc.StepCache:
        return self._cache

    def stats(self) -> dict:
        """Compile/dispatch counters (the step cache's, unchanged)."""
        return self._cache.stats()

    # -- compilation -------------------------------------------------------

    def _jit(self, program: Program):
        """The ONE ``jax.jit`` call of the library's step dispatch.
        Memoized per Program instance so the diagnostic surface
        (:meth:`jit`) and the cached dispatch path share a single jitted
        callable."""
        if program._jitted is None:
            fn = program.fn if program.wrap is None else program.wrap(
                program.fn)
            kw: dict = {}
            if program.in_shardings is not _UNSET:
                kw["in_shardings"] = program.in_shardings
            if program.out_shardings is not _UNSET:
                kw["out_shardings"] = program.out_shardings
            program._jitted = jax.jit(
                fn, donate_argnums=program.donate_argnums, **kw)
        return program._jitted

    def jit(self, program: Program):
        """Build (without caching or counting) the jitted callable for a
        Program — the diagnostic surface: tests ``.lower()`` the result
        to inspect shardings / aliasing without dispatching."""
        return self._jit(program)

    def compile(self, program: Program, args):
        """Resolve ``program`` for ``args`` through the step cache
        (compile on miss, LRU hit otherwise) without dispatching."""
        return self._cache.program(program.kind, program.static_key, args,
                                   lambda: self._jit(program))

    # -- dispatch ----------------------------------------------------------

    def submit(self, program: Program, args, *, step: Optional[int] = None):
        """Compile-or-hit, count, span, heartbeat, dispatch.

        ``step``: the caller's 1-based step count for the watchdog
        heartbeat (train and serve kinds; dispatch returning means the
        host made forward progress — execution is async, a wedged
        backend blocks the dispatch itself); the ``dispatch`` span
        carries it.  Eager kinds pass None: they neither span nor
        heartbeat.
        """
        beat = (program.kind in TRAIN_KINDS or program.kind in SERVE_KINDS
                or program.kind in ROLLOUT_KINDS)
        span = contextlib.nullcontext()
        if beat:
            tags = {"kind": program.kind}
            if step is not None:
                tags["step"] = step
            if _CLUSTER_EPOCH is not None:
                tags["cluster_epoch"] = _CLUSTER_EPOCH
            span = _spans.span("dispatch", **tags)
        # the span covers all of what enqueuing costs the host: the
        # cache lookup (a signature over every argument leaf) too
        with span:
            fn = self.compile(program, args)
            self._cache._bump("dispatches", program.kind)
            out = fn(*args)
        if beat and step is not None:
            _obs_watchdog.heartbeat(step=step)
        return out

    # -- async H2D double-buffering ---------------------------------------

    def drive(self, step, loader, *, max_steps: Optional[int] = None,
              **prefetch_kwargs):
        """Run a train step over a loader with the next window's H2D
        transfer overlapped under the current window's dispatch.

        ``loader`` is either a :class:`~apex_tpu.runtime.data.
        DataPrefetcher` (used as-is) or any host batch iterable, wrapped
        in one (``prefetch_kwargs`` forwarded — pass ``accum_steps=K``
        for stacked accumulation windows).  The prefetcher's bounded
        depth-2 queue is the executor's two-deep device-side input
        buffer: its worker thread issues exactly one ``span("h2d")``
        transfer per window, and because step dispatch is async the
        transfer for window N+1 is in flight while window N computes.
        Respecting the ``h2d`` overlap knob, ``overlap_enabled("h2d")
        is False`` degrades to a single-buffered (depth-1) queue —
        transfer and compute serialize, which is the overlap-off arm
        the microbenchmark measures.  Returns the list of per-window
        losses.
        """
        from .data import DataPrefetcher

        own = not isinstance(loader, DataPrefetcher)
        if own:
            prefetch_kwargs.setdefault(
                "depth", 2 if overlap_enabled("h2d") else 1)
            loader = DataPrefetcher(loader, **prefetch_kwargs)
        losses = []
        try:
            for batch in loader:
                losses.append(step(*batch))
                if max_steps is not None and len(losses) >= max_steps:
                    break
        finally:
            if own:
                loader.close()
        return losses


#: process-global executor shared by every surface
executor = Executor()


def drain_telemetry(step) -> Optional[dict]:
    """Host-sync a step's on-device telemetry accumulator and reset it.

    The shared carry-drain for every step kind (fused ``TrainStep``,
    GSPMD ``ZeroTrainStep``, planned shard_map steps): the ONE
    deliberate host round-trip of the telemetry path, in eager code
    outside jit, so the compiled window program stays 1 compile +
    1 dispatch.  Emits a ``train.telemetry`` event + gauges and returns
    the record (None when telemetry is off or no window completed since
    the last drain).  ``step`` needs ``.state`` (a StepState) and
    ``.calls``.
    """
    telem = step.state.telem
    if telem is None:
        return None
    host = jax.device_get(telem)
    windows = int(host.windows)
    if windows == 0:
        return None
    rec = _obs.event(
        "train.telemetry",
        step=step.calls,
        windows=windows,
        loss_mean=float(host.loss_sum) / windows,
        grad_norm=float(host.grad_norm),
        loss_scale=float(host.loss_scale),
        overflow_count=int(host.overflow_count))
    _obs.gauge("train.loss").set(rec["loss_mean"])
    _obs.gauge("train.grad_norm").set(rec["grad_norm"])
    _obs.gauge("train.loss_scale").set(rec["loss_scale"])
    _obs.counter("train.overflow_windows").inc(rec["overflow_count"])
    step.state = step.state._replace(telem=_obs_telemetry.init_telemetry())
    return rec


# ---------------------------------------------------------------------------
# Whole-optimizer step programs (the eager surface, migrated here from
# step_cache — PR 1's routes now submit Program descriptors like
# everything else)
# ---------------------------------------------------------------------------
#
# ``update(static_cfg, donated, grads, hyper, flag) -> new_donated`` is a
# module-level pure function supplied by each optimizer; ``donated`` holds
# params + optimizer state (+ fp16 model copies under amp O2), ``grads`` the
# consumed gradients, ``hyper`` the traced scalar hyperparameters.


def optimizer_step(kind: str, static_cfg, update, flag, donated, grads,
                   hyper):
    """Dispatch one optimizer step as a single cached XLA executable.

    Donates ``donated`` (params + optimizer state): the caller must rebind
    every returned leaf and drop references to the inputs.

    No ``lax.cond`` here: on this path the overflow flag is reference-exact
    semantics — the Adam/LAMB/NovoGrad kernels deliberately ignore it
    (multi_tensor_adam.cu:40-41) and the SGD op gates on it internally —
    and an XLA conditional would copy the whole donated tree at the branch
    boundary every step.  The fused amp path
    (:func:`optimizer_step_with_scaler`), where a skip can actually occur,
    is the one that wraps the update in ``lax.cond``.
    """
    donate = donation.enabled

    def run(flag, donated, grads, hyper):
        return update(static_cfg, donated, grads, hyper, flag)

    prog = Program(kind, (static_cfg, donate), run,
                   donate_argnums=(1,) if donate else ())
    return executor.submit(prog, (flag, donated, grads, hyper))


def optimizer_step_with_scaler(kind: str, static_cfg, update, scaler_state,
                               scaler_cfg, donated, grads, hyper):
    """The fully-fused amp step: overflow-conditional optimizer update AND
    dynamic-loss-scale update in one executable, with the scaler state
    donated alongside params/optimizer state.  Zero host round-trips: the
    skip decision is ``lax.cond`` on the scaler's on-device overflow flag.

    ``scaler_cfg``: hashable kwargs tuple for
    :func:`apex_tpu.amp.scaler.update_scale_state`.
    Returns ``(new_scaler_state, new_donated)``.
    """
    from ..amp.scaler import update_scale_state

    donate = donation.enabled
    kw = dict(scaler_cfg)

    def run(sstate, donated, grads, hyper):
        flag = sstate.overflow
        new_d = lax.cond(
            flag > 0, lambda d: d,
            lambda d: update(static_cfg, d, grads, hyper,
                             jnp.zeros((), jnp.int32)), donated)
        new_s, _ = update_scale_state(sstate, **kw)
        return new_s, new_d

    prog = Program(kind, (static_cfg, scaler_cfg, donate), run,
                   donate_argnums=(0, 1) if donate else ())
    return executor.submit(prog, (scaler_state, donated, grads, hyper))


# ---------------------------------------------------------------------------
# amp programs: unscale / grad-accumulate / master→model copy
# ---------------------------------------------------------------------------


def unscale(flag, model_grads, out_dtypes, inv_scale,
            check_overflow: bool = True):
    """Whole-step grad unscale + overflow check as one executable
    (``master = model_grad * inv_scale``, flag set on non-finite inputs).
    Returns ``(new_flag, master_grads)``.
    """
    out_names = tuple(jnp.dtype(d).name for d in out_dtypes)

    def run(flag, grads, inv):
        from .. import ops
        outs = [jnp.zeros(g.shape, d) for g, d in zip(grads, out_names)]
        new_flag, new = ops.multi_tensor_scale(
            flag, [list(grads), outs], inv)
        return (new_flag if check_overflow else flag), new

    prog = Program("amp_unscale", (out_names, bool(check_overflow)), run)
    return executor.submit(
        prog, (flag, list(model_grads), jnp.asarray(inv_scale, _f32)))


def unscale_with_stashed(flag, model_grads, stashed_grads, a, b):
    """Fused ``out = a*model + b*stashed`` accumulation (one executable),
    flagging non-finite model grads.  Returns ``(new_flag, master_grads)``.
    """

    def run(flag, model, stashed, a, b):
        from .. import ops
        outs = [jnp.zeros(s.shape, s.dtype) for s in stashed]
        return ops.multi_tensor_axpby(
            flag, [list(model), list(stashed), outs], a, b, 0)

    prog = Program("amp_axpby", (), run)
    return executor.submit(
        prog, (flag, list(model_grads), list(stashed_grads),
               jnp.asarray(a, _f32), jnp.asarray(b, _f32)))


def master_to_model(masters, model_vals):
    """fp32 master → half model copy as one executable, donating the stale
    model buffers (each output aliases the old copy it replaces)."""
    donate = donation.enabled

    def run(masters, old):
        return [m.astype(o.dtype) for m, o in zip(masters, old)]

    prog = Program("amp_master_to_model", (donate,), run,
                   donate_argnums=(1,) if donate else ())
    return executor.submit(prog, (list(masters), list(model_vals)))
