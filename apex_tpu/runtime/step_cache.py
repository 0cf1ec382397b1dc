"""Single-executable, donated step-program cache for the eager optimizer
surface (``amp.initialize`` + ``optimizer.step()`` — the path the imagenet /
dcgan / simple examples drive).

Before this cache the eager surface dispatched one jitted executable per
param-group × dtype bucket with static hyperparameters and no buffer
donation: every step re-allocated params + both Adam moments (3× param
memory churn) and any lr/wd/beta schedule retraced the whole update — the
per-step weight-update overhead that arxiv 2004.13336 identifies as a
first-order cost of data-parallel training.  Here the ENTIRE update — grad
unscale + overflow check (``amp/scaler.py``), per-group optimizer math for
all groups and dtype buckets, conditional skip via ``lax.cond``, and the
dynamic-loss-scale update — compiles into ONE XLA executable per optimizer:

* keyed on (pytree structure, leaf shapes/dtypes, static config) — the same
  things ``jax.jit`` retraces on, so cache misses == XLA compiles and
  ``stats()`` makes retrace regressions observable;
* ``donate_argnums`` on params, optimizer state and scaler state — XLA
  writes the new params/moments into the old buffers (``tf.aliasing_output``
  in the lowered HLO), so steady-state optimizer stepping allocates nothing.
  Donation follows the "auto" policy: on for tpu/gpu, off for cpu (XLA cpu
  accepts donate_argnums but degrades it to defensive copies — measured 2×
  step time; see :func:`set_donation`).  Consequence when on: any reference
  to a PRE-step ``p.data`` (or moment array) a caller stashed is
  invalidated by the step — copy first if you need it;
* all scalar hyperparameters (lr, betas, eps, weight_decay, step) enter as
  traced device scalars, so lr/wd/beta schedules never recompile.

The stateful optimizers (``apex_tpu.optimizers``, ``contrib.optimizers``)
collect their ``param_groups`` into pure pytrees and dispatch here; the amp
hooks (``_process_optimizer``, ``handle.scale_loss``) route the unscale /
master→model copy / deferred scale update through the same cache.
"""
from __future__ import annotations

import threading
from collections import OrderedDict

import jax
import jax.numpy as jnp

from ..observe import registry as _obs

def _leaf_sig(leaf):
    return (tuple(leaf.shape), jnp.dtype(leaf.dtype).name)


def signature(tree):
    """Hashable (treedef, leaf shapes/dtypes) key for an argument pytree —
    exactly what jit retraces on (all leaves enter strongly typed)."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return (treedef, tuple(_leaf_sig(l) for l in leaves))


def _example_avals(tree):
    return jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(tuple(l.shape), jnp.dtype(l.dtype)),
        tree)


class StepCache:
    """Compiled step-program cache with compile/dispatch counters.

    One entry per (kind, static config, argument signature); entries hold
    the jitted callable plus a ShapeDtypeStruct example tree so callers
    (tests, tooling) can re-lower a cached program without live arrays.
    LRU-capped so dead parameter sets cannot pin executables forever.
    """

    _TOP_COUNTERS = ("compiles", "cache_hits", "dispatches",
                     "multi_tensor_calls")
    _KIND_COUNTERS = ("compiles", "cache_hits", "dispatches")

    def __init__(self, cap: int = 128, metrics_prefix: str = "step_cache."):
        self._cap = cap
        self._prefix = metrics_prefix
        self._registry = _obs.get_registry()
        self._lock = threading.RLock()
        self._programs: OrderedDict = OrderedDict()
        self.reset_stats()

    # -- stats -------------------------------------------------------------
    # Counters live in the apex_tpu.observe registry (names
    # ``step_cache.<counter>`` / ``step_cache.kind.<kind>.<counter>``);
    # ``stats()`` reconstructs the historical dict shape from them so the
    # public surface — and every test pinned to it — is unchanged.

    def reset_stats(self):
        self._registry.remove(self._prefix)

    def _bump(self, name, kind=None):
        self._registry.counter(self._prefix + name).inc()
        if kind is not None:
            self._registry.counter(
                f"{self._prefix}kind.{kind}.{name}").inc()

    def stats(self) -> dict:
        """Counters for regression tracking.

        ``compiles`` is the analogue of the reference's kernel-*build* cost
        (one per new program shape), ``dispatches`` of its per-step kernel
        *launch* count — except one dispatch here covers what the CUDA
        reference spreads over dozens of ``multi_tensor_*`` launches.
        ``multi_tensor_calls`` counts eager multi-tensor op invocations for
        a direct launch-count comparison with the reference.
        """
        counters = self._registry.snapshot()["counters"]
        out = {n: counters.get(self._prefix + n, 0)
               for n in self._TOP_COUNTERS}
        by_kind: dict = {}
        kind_prefix = self._prefix + "kind."
        for full, value in counters.items():
            if not full.startswith(kind_prefix):
                continue
            kind, _, cname = full[len(kind_prefix):].rpartition(".")
            if kind and cname in self._KIND_COUNTERS:
                by_kind.setdefault(
                    kind, {n: 0 for n in self._KIND_COUNTERS})[cname] = value
        with self._lock:
            out["programs"] = len(self._programs)
        out["by_kind"] = by_kind
        return out

    # -- cache -------------------------------------------------------------
    def program(self, kind: str, static_key, args, build):
        """Return the compiled program for ``args``, building on a miss.

        ``static_key`` must be hashable and capture every Python-level value
        the built program closes over; ``args`` is the exact argument tuple
        the program will be called with (its structure + shapes/dtypes
        complete the key).
        """
        key = (kind, static_key, signature(args))
        with self._lock:
            entry = self._programs.pop(key, None)
            if entry is not None:
                self._programs[key] = entry     # pop + reinsert = LRU
                self._bump("cache_hits", kind)
                return entry["fn"]
        fn = build()
        with self._lock:
            while len(self._programs) >= self._cap:
                self._programs.popitem(last=False)
            self._programs[key] = {"kind": kind, "fn": fn,
                                   "example": _example_avals(args)}
            self._bump("compiles", kind)
        return fn

    def entries(self):
        """Snapshot of cached programs: [{kind, fn, example}] — ``example``
        is a ShapeDtypeStruct tree accepted by ``fn.lower(*example)``."""
        with self._lock:
            return [dict(e) for e in self._programs.values()]

    def clear(self):
        with self._lock:
            self._programs.clear()

    def discard(self, match) -> int:
        """Drop every entry whose ``(kind, static_key)`` satisfies
        ``match``; returns how many.  For programs that can never be hit
        again (their static key names an owner that is gone): an entry
        holds its program's closure, and with it whatever that closes
        over, until the LRU turns it out."""
        with self._lock:
            dead = [k for k in self._programs if match(k[0], k[1])]
            for k in dead:
                del self._programs[k]
        return len(dead)


#: process-global cache shared by every optimizer / amp hook
step_cache = StepCache()


def set_donation(mode):
    """Set the donation policy: True, False, or "auto" (default).

    Delegate onto :data:`apex_tpu.runtime.executor.donation` — the one
    :class:`~apex_tpu.runtime.executor.DonationPolicy` every surface
    shares (the policy used to be re-derived here, in training/step.py
    and in the amp handle).  Kept under the historical name.
    """
    from . import executor
    executor.donation.set(mode)


def donation_enabled() -> bool:
    from . import executor
    return executor.donation.enabled


def stats() -> dict:
    return step_cache.stats()


def kind_stats(kind: str) -> dict:
    """One kind's ``{compiles, cache_hits, dispatches}`` (zeros if the
    kind never dispatched) — the serve engine's recompile-free-decode
    bound reads ``kind_stats("decode_step")["compiles"]`` and asserts
    it stays <= the bucket count after warmup."""
    return stats()["by_kind"].get(
        kind, {n: 0 for n in StepCache._KIND_COUNTERS})


def reset_stats():
    step_cache.reset_stats()


def clear():
    step_cache.clear()


def record_multi_tensor_call():
    step_cache._bump("multi_tensor_calls")


def static_plan_key(plan):
    """Normalize a ``parallel.auto.Plan`` (or None) into the hashable
    tuple program keys embed — the historical ``(dp, tp, sp, zero_stage,
    accum, chunked_loss)`` 6-tuple, plus tagged string segments
    (``"pp4"``, ``"micro8"``, ``"remat=selective"``, ``"ep8"``,
    ``"offopt=1"``, ``"offact=0.5"``) appended only when a v3 axis is
    non-default, so pre-v3 keys are unchanged.  ``plan_from_key``
    inverts it.  Threading the plan through the STATIC key keeps
    compiled executables per-plan observables: two plans that would
    otherwise collide on signature (same shapes, different mesh
    factorization driven by the wrapper) never share a program entry,
    and ``stats()['by_kind']`` stays meaningful under ``parallel=``.
    None (an unplanned step) passes through as None."""
    if plan is None:
        return None
    return tuple(plan.key())


# The whole-optimizer / amp step programs that used to live here
# (optimizer_step, optimizer_step_with_scaler, unscale,
# unscale_with_stashed, master_to_model) moved to
# ``apex_tpu.runtime.executor`` — the one dispatch choke point both the
# eager and the fused surface now submit Program descriptors to.  This
# module keeps only the cache itself and its stats surface.
