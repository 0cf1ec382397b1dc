"""What the kernel modules share: the backend mode, the registry the
jaxpr verifier walks, and the tally of which tier a call took.

* **backend mode** (:func:`pallas_mode` / :func:`force_mode`):
  'compiled' on TPU, 'interpret' for CPU kernel testing, ``None`` for
  the pure-jnp fallback.  Dispatch happens at trace time; already-jitted
  callables keep the mode they traced with.

* **tier** — which of a kernel's two paths a call takes is a rule in
  the kernel's own module, a pure function of this mode and the
  operands' shapes and dtypes (``docs/kernels.md`` has the table and
  each rule's receipt).  No file and no environment variable takes part.
  Every kernel registers with its declared XLA fallback
  (:func:`register_kernel`; the KERNEL-FALLBACK lint rule enforces it),
  and a rule answers through :func:`choose`, so the
  ``kernels.dispatch.<kernel>.<tier>`` counters say which path each
  traced program took.
"""
from __future__ import annotations

import contextlib
import dataclasses
import os
from typing import Callable, Optional

import jax

_forced = [None]


def pallas_mode():
    """Returns 'compiled' | 'interpret' | None (use the jnp fallback).

    A ``force_mode()`` scope wins; otherwise the mode follows the
    backend the process runs on — 'compiled' on TPU, the jnp path
    anywhere else.  Nothing outside the program (no environment
    variable) selects it.
    """
    if _forced[0] is not None:
        return None if _forced[0] == "off" else _forced[0]
    return "compiled" if jax.default_backend() == "tpu" else None


@contextlib.contextmanager
def force_mode(mode):
    """Force kernel dispatch for a scope: 'compiled', 'interpret' or 'off'.

    Note: dispatch happens at trace time, so already-jitted callables keep
    the mode they were traced with.
    """
    prev = _forced[0]
    _forced[0] = mode
    try:
        yield
    finally:
        _forced[0] = prev


# The masked-vocabulary convention, in one place: logits at MASKED_FILL
# (-1e30) mean "this column does not exist" (lane-padded heads'
# pad columns, nucleus-filtered tokens); consumers treat anything at or
# below MASKED_LOGIT_THR (-1e29) as masked — softmax contributions
# underflow to 0 there, and the smoothing-aware losses
# (nn.functional.cross_entropy, contrib.xentropy) exclude such columns
# from the label-smoothing term and its divisor.
MASKED_FILL = -1e30
MASKED_LOGIT_THR = -1e29


# Round-5 norm-kernel verdict (unledgered run, round 5).  The
# variance-controlled isolated A/B (median of 5 interleaved reps)
# put every LN/RMS row in a 0.93-1.03x band around XLA's own fusion —
# the round-3 "1.73x LN win" was single-run noise — and the IN-STEP
# A/B then showed routing norms to XLA is a real headline win:
# BERT 1178->1252 (+6.3%), GPT 1044->1067 (+2.2%), Llama 1396->1469
# (+5.2%) seq/s.  A Pallas custom call is a fusion barrier; XLA fuses
# the norm into its producers/consumers when allowed to own it.
# Default therefore defers to XLA on compiled TPU; the kernels stay
# for interpret-mode parity coverage and APEX_TPU_NORM_KERNEL=1 opts
# back in on-chip.
_NORM_KERNEL_DEFAULT_ON = False


def norm_kernel_mode():
    """Effective dispatch mode for the LayerNorm/RMSNorm Pallas
    kernels: ``pallas_mode()`` gated by APEX_TPU_NORM_KERNEL
    ('auto'/'1'/'0') on compiled backends.  A ``force_mode`` scope
    overrides the gate (parity checks and tests force the kernel arm
    explicitly and must never silently self-compare); interpret mode
    always exercises the kernels — that mode exists to test them."""
    if _forced[0] is not None:
        return pallas_mode()
    mode = pallas_mode()
    if mode != "compiled":
        return mode
    env = os.environ.get("APEX_TPU_NORM_KERNEL", "auto").lower()
    if env in ("1", "on"):
        return mode
    if env in ("0", "off"):
        return None
    return mode if _NORM_KERNEL_DEFAULT_ON else None


# ---------------------------------------------------------------------------
# Kernel registry
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    """One registered kernel and its declared XLA fallback."""
    name: str
    xla_fallback: str            # where the XLA path lives (dotted path)
    doc: str = ""
    # () -> [(tier, fn, example_avals)] — the jaxpr verifier
    # (lint.jaxpr_audit) traces both tiers abstractly through this
    audit_programs: Optional[Callable] = None


KERNELS: dict = {}


def register_kernel(name: str, *, xla_fallback: str, doc: str = "",
                    audit_programs: Optional[Callable] = None) -> KernelSpec:
    """Register a kernel.  ``xla_fallback`` is mandatory by construction
    — the KERNEL-FALLBACK lint rule flags registrations without it.
    ``audit_programs`` makes both tiers traceable by the jaxpr
    verifier: a zero-arg callable yielding ``(tier, fn, example)``
    triples with abstract (ShapeDtypeStruct) examples."""
    if not xla_fallback:
        raise ValueError(
            f"kernel {name!r} must declare an XLA fallback "
            f"(KERNEL-FALLBACK)")
    spec = KernelSpec(name=name, xla_fallback=xla_fallback, doc=doc,
                      audit_programs=audit_programs)
    KERNELS[name] = spec
    return spec


def catalog() -> dict:
    """Snapshot of the registered kernels (name -> KernelSpec)."""
    return dict(KERNELS)


def choose(kernel: str, *, fits: bool = True, compiled: bool = True):
    """What every kernel's rule shares.  The module says whether the
    kernel's tiles take these operands at all (``fits``) and whether a
    ``compiled`` program should give them to it; this answers with the
    mode the kernel runs in, or ``None`` for the XLA tier.  Interpret
    mode takes the kernel wherever it fits (that mode exists to exercise
    it), and where :func:`pallas_mode` is ``None`` so is the answer.  The
    choice is counted where it is made, at trace time, as
    ``kernels.dispatch.<kernel>.pallas`` or ``.xla`` (here, so that no
    kernel module imports ``observe``)."""
    mode = pallas_mode()
    if not fits or (mode == "compiled" and not compiled):
        mode = None
    tally(kernel, "xla" if mode is None else "pallas")
    return mode


def tally(kernel: str, path: str):
    """Count one traced call of ``kernel`` under
    ``kernels.dispatch.<kernel>.<path>``: the tier where :func:`choose`
    answers, and what else a kernel's module chooses between from its
    operands (the flash kernels' ``resident`` path)."""
    from ..observe import registry as _obs
    # tpu-lint: disable=OBS-IN-JIT deliberate trace-time telemetry: the
    # counter says which path each traced program took, once a trace
    _obs.counter(f"kernels.dispatch.{kernel}.{path}").inc()


# ---------------------------------------------------------------------------
# Executor-dispatched kernel programs (the eager tier surface)
# ---------------------------------------------------------------------------


def run(name: str, pallas: bool, args, *, pallas_fn: Callable,
        xla_fn: Callable, static_key=(), donate_argnums=()):
    """Dispatch one kernel call as an executor Program whose KIND names
    the tier the caller's rule chose — ``kernel.<name>.<tier>`` — so
    ``step_cache.kind_stats("kernel.multi_tensor_adam.xla")`` pins
    which path a call actually took.

    Donation-safe: ``donate_argnums`` is resolved through the one
    :class:`~apex_tpu.runtime.executor.DonationPolicy` and the resolved
    flag joins the static key, exactly like the optimizer-step programs.
    """
    from ..runtime import executor as _executor

    fn, tier = (pallas_fn, "pallas") if pallas else (xla_fn, "xla")
    donate = _executor.donation.enabled and bool(donate_argnums)

    def kernel_run(*a):
        return fn(*a)

    prog = _executor.Program(
        f"kernel.{name}.{tier}", (static_key, pallas_mode(), donate),
        kernel_run,
        donate_argnums=tuple(donate_argnums) if donate else ())
    return _executor.executor.submit(prog, tuple(args))
