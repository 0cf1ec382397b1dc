"""Kernel dispatch policy: backend selection + the measured-threshold
tier decision every Pallas kernel routes through.

Two layers live here:

* **backend mode** (:func:`pallas_mode` / :func:`force_mode`) — moved
  from ``ops/pallas/__init__.py``: 'compiled' on TPU, 'interpret' for
  CPU kernel testing, ``None`` for the pure-jnp fallback.  Dispatch
  happens at trace time; already-jitted callables keep the mode they
  traced with.

* **tier policy** (:func:`register_kernel` / :func:`decide` /
  :func:`run`) — the round-5 lesson turned into machinery.  Three
  kernel candidates were gated off as frozen constants (norms -> XLA
  default, flash only >= 512 keys, lm_head_xent 0.69x); this module
  makes the gate *data*: every kernel registers with a declared XLA
  fallback and a threshold probe (the KERNEL-FALLBACK lint rule
  enforces both), :func:`decide` consults the calibration ledger
  (:mod:`apex_tpu.kernels.ledger`) at trace time — a static, hashable
  decision, no host sync inside jit — and falls back to XLA below the
  kernel's measured win region.  Every decision is emitted once as a
  ``kernels.dispatch`` observe event carrying the ledger entry that
  made it, so dispatch is auditable from the event log alone.
"""
from __future__ import annotations

import contextlib
import dataclasses
import os
import threading
from typing import Callable, Optional

import jax

from . import ledger as _ledger

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
# Shape fingerprints — the ledger key half the chip doesn't supply
# ---------------------------------------------------------------------------


def shape_fp(**dims) -> str:
    """Canonical fingerprint: sorted ``k=v`` pairs joined by ','.

    The SAME helper builds the key at probe time (bench), decision time
    (dispatch) and pricing time (planner) — matching by construction."""
    return ",".join(f"{k}={dims[k]}" for k in sorted(dims))


def parse_fp(fp: str) -> dict:
    """Inverse of :func:`shape_fp`; int-valued where possible."""
    out = {}
    for part in str(fp).split(","):
        k, _, v = part.partition("=")
        if not k:
            continue
        try:
            out[k] = int(v)
        except ValueError:
            out[k] = v
    return out


def attention_fp(b, h, sq, sk, d, dtype="float32", causal=False) -> str:
    return shape_fp(b=int(b), h=int(h), sq=int(sq), sk=int(sk), d=int(d),
                    dtype=str(dtype), causal=int(bool(causal)))


def multi_tensor_fp(op: str, n_elements: int, n_tensors: int,
                    dtype="float32") -> str:
    return shape_fp(op=str(op), n=int(n_elements), t=int(n_tensors),
                    dtype=str(dtype))


def vocab_chain_fp(n, v, e, dtype="float32") -> str:
    return shape_fp(n=int(n), v=int(v), e=int(e), dtype=str(dtype))


# ---------------------------------------------------------------------------
# Kernel registry
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    """One registered kernel: its declared XLA fallback and the default
    threshold probe that decides dispatch when the ledger has no
    measurement for the shape (the probe encodes the frozen round-5
    receipts; the ledger overrides it with live data)."""
    name: str
    xla_fallback: str            # where the XLA path lives (dotted path)
    threshold_probe: Callable    # (dims: dict) -> (threshold, use_pallas)
    doc: str = ""
    # () -> [(tier, fn, example_avals)] — the jaxpr verifier
    # (lint.jaxpr_audit) traces both tiers abstractly through this
    audit_programs: Optional[Callable] = None


KERNELS: dict = {}


def register_kernel(name: str, *, xla_fallback: str,
                    threshold_probe: Callable, doc: str = "",
                    audit_programs: Optional[Callable] = None) -> KernelSpec:
    """Register a kernel with the dispatch policy.  Both ``xla_fallback``
    and ``threshold_probe`` are mandatory by construction — the
    KERNEL-FALLBACK lint rule flags registrations without them.
    ``audit_programs`` makes both tiers traceable by the jaxpr
    verifier: a zero-arg callable yielding ``(tier, fn, example)``
    triples with abstract (ShapeDtypeStruct) examples."""
    if not xla_fallback or threshold_probe is None:
        raise ValueError(
            f"kernel {name!r} must declare an XLA fallback and a "
            f"threshold probe (KERNEL-FALLBACK)")
    spec = KernelSpec(name=name, xla_fallback=xla_fallback,
                      threshold_probe=threshold_probe, doc=doc,
                      audit_programs=audit_programs)
    KERNELS[name] = spec
    return spec


def catalog() -> dict:
    """Snapshot of the registered kernels (name -> KernelSpec)."""
    return dict(KERNELS)


# ---------------------------------------------------------------------------
# The tier decision
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Decision:
    """One dispatch decision — hashable and static (safe inside jit
    tracing; nothing here touches device values)."""
    kernel: str
    tier: str                    # "pallas" | "xla"
    shape_fp: str
    chip: str
    source: str                  # "ledger" | "probe" | "mode"
    threshold: Optional[float] = None
    win: Optional[float] = None


_decisions_lock = threading.Lock()
_decisions: dict = {}            # (kernel, fp, mode, chip) -> Decision


def decide(name: str, fp: str) -> Decision:
    """Pick the tier for ``(kernel, shape)`` at trace time.

    Policy, in order: no Pallas backend -> XLA; a ledger entry for
    ``(chip, kernel, fp)`` -> its measured verdict (win >= 1 runs the
    kernel, win < 1 falls back — in interpret mode too, so the policy
    itself is testable on CPU); otherwise the kernel's registered
    threshold probe (interpret mode with no entry defaults to the
    kernel — that mode exists to exercise it).  The first decision per
    key emits a ``kernels.dispatch`` observe event carrying the ledger
    entry that made it.
    """
    mode = pallas_mode()
    chip = _ledger.chip_name()
    key = (name, fp, mode, chip)
    with _decisions_lock:
        hit = _decisions.get(key)
    if hit is not None:
        return hit

    entry = None
    if mode is None:
        d = Decision(name, "xla", fp, chip, "mode")
    else:
        entry = _ledger.get_ledger().lookup_kernel(chip, name, fp)
        if entry is not None:
            tier = "pallas" if entry["win"] >= 1.0 else "xla"
            d = Decision(name, tier, fp, chip, "ledger",
                         threshold=entry.get("threshold"),
                         win=entry["win"])
        else:
            spec = KERNELS.get(name)
            if spec is None:
                d = Decision(name, "pallas", fp, chip, "mode")
            elif mode == "interpret":
                d = Decision(name, "pallas", fp, chip, "mode")
            else:
                threshold, use_pallas = spec.threshold_probe(parse_fp(fp))
                d = Decision(name, "pallas" if use_pallas else "xla", fp,
                             chip, "probe", threshold=threshold)

    with _decisions_lock:
        first = key not in _decisions
        _decisions[key] = d
    if first:
        from ..observe import registry as _obs
        # tpu-lint: disable=OBS-IN-JIT deliberate trace-time telemetry:
        # decide() runs while tracing and the dispatch event must fire
        # exactly ONCE per new (kernel, shape, mode, chip) decision —
        # once-at-trace-time is the contract here, not dead telemetry
        _obs.event("kernels.dispatch", kernel=d.kernel, tier=d.tier,
                   shape_fp=d.shape_fp, chip=d.chip, source=d.source,
                   threshold=d.threshold, win=d.win,
                   ledger_entry=entry)
        # tpu-lint: disable=OBS-IN-JIT same contract as the event above:
        # the per-tier counter increments once per new decision
        _obs.counter(f"kernels.dispatch.{d.kernel}.{d.tier}").inc()
    return d


def decisions() -> list:
    """Snapshot of every decision taken so far (bench headline stages
    attach this to their records so throughput is attributable per
    kernel tier)."""
    with _decisions_lock:
        return [dataclasses.asdict(d) for d in _decisions.values()]


def reset_decisions() -> None:
    """Forget cached decisions (tests; also required after the ledger
    is re-pointed — decisions embed ledger verdicts)."""
    with _decisions_lock:
        _decisions.clear()


def measured_threshold(name: str, dim: str, default: int) -> int:
    """A measured dispatch threshold for ``kernel`` along fingerprint
    dimension ``dim``: the smallest probed value of ``dim`` whose entry
    wins (xla_us/pallas_us >= 1).  Falls back to ``default`` when the
    ledger has no winning entry for this chip — the frozen prior keeps
    deciding until someone measures."""
    entries = _ledger.get_ledger().kernel_entries(_ledger.chip_name(), name)
    winners = []
    for fp, rec in entries.items():
        win = rec.get("win")
        val = parse_fp(fp).get(dim)
        if isinstance(val, int) and isinstance(win, (int, float)) \
                and win >= 1.0:
            winners.append(val)
    return min(winners) if winners else default


# ---------------------------------------------------------------------------
# Executor-dispatched kernel programs (the eager tier surface)
# ---------------------------------------------------------------------------


def run(name: str, fp: str, args, *, pallas_fn: Callable,
        xla_fn: Callable, static_key=(), donate_argnums=()):
    """Dispatch one kernel call as an executor Program whose KIND names
    the tier — ``kernel.<name>.<tier>`` — so
    ``step_cache.kind_stats("kernel.flash_attention.xla")`` pins which
    path a shape actually took (the dispatch-policy acceptance test).

    Donation-safe: ``donate_argnums`` is resolved through the one
    :class:`~apex_tpu.runtime.executor.DonationPolicy` and the resolved
    flag joins the static key, exactly like the optimizer-step programs.
    """
    from ..runtime import executor as _executor

    d = decide(name, fp)
    fn = pallas_fn if d.tier == "pallas" else xla_fn
    donate = _executor.donation.enabled and bool(donate_argnums)

    def kernel_run(*a):
        return fn(*a)

    prog = _executor.Program(
        f"kernel.{name}.{d.tier}", (static_key, fp, donate), kernel_run,
        donate_argnums=tuple(donate_argnums) if donate else ())
    return _executor.executor.submit(prog, tuple(args))
