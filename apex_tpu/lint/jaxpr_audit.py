"""jaxpr-level program verifier: trace the library's REAL entry programs
and check invariants on the IR itself.

The AST rules in :mod:`.rules` judge source; this module judges what jax
actually stages.  It runs tiny CPU workloads through the same entry
points production uses — the fused train step (``make_train_step``),
the eager optimizer executor (``FusedAdam.step``), the serving engine
(``ServeEngine.run``), and every registered kernel's BOTH tiers — then
audits the resulting jaxprs:

* **no-callbacks** — ``pure_callback`` / ``io_callback`` /
  ``debug_callback`` never appear in a train or serve program (a
  callback is a hidden host round-trip per dispatch; the IR-level twin
  of HOST-SYNC).
* **scan-collective** — collectives sit at scan boundaries, never
  inside a scan body (the jaxpr-level SCAN-COLLECTIVE; ``ppermute``
  pipeline rotations are exempt, matching the AST rule).
* **scan-carry-fp32** — no fp16/bf16 floating carry in a train-step
  ``lax.scan``: gradient windows accumulate in fp32 (integer and key
  carries are fine; it is HALF accumulators that silently lose mantissa
  over a window).
* **donation-census** — with the donation policy forced on, the lowered
  HLO of donated programs aliases input buffers to outputs
  (``tf.aliasing_output``), generalizing
  tests/test_executor.py::test_donation_alias_in_lowered_hlo to every
  cached program of a donated kind.
* **telemetry-carry** — turning ``telemetry=True`` grows the train
  step's jaxpr by EXACTLY the telemetry carry leaves, on both the input
  and the output side: observability rides the state carry and adds
  zero extra outputs (the zero-dispatch contract of apex_tpu.observe).

Programs are collected once per process (memoized) — the audit traces
abstractly where it can and runs one tiny concrete step where the
program cache is populated by execution.  Exposed as
``python -m apex_tpu.lint --jaxpr`` and the tier-1 gate in
tests/test_jaxpr_audit.py.
"""
from __future__ import annotations

import dataclasses
import os
import time
from typing import Callable, List, Optional, Sequence, Tuple

# must win before the first jax backend lookup: the audit traces on CPU
os.environ.setdefault("JAX_PLATFORMS", "cpu")

#: primitives that smuggle a host call into a compiled program
CALLBACK_PRIMS = frozenset({
    "pure_callback", "io_callback", "debug_callback", "callback",
})

#: cross-device primitives whose placement the scan rule polices
COLLECTIVE_PRIMS = frozenset({
    "psum", "pmean", "pmax", "pmin", "all_gather", "all_to_all",
    "reduce_scatter", "psum_scatter",
})

#: exempt inside scans: pipeline/ring rotations are per-iteration by
#: design (mirrors rules.ScanCollective._rotation_only)
ROTATION_PRIMS = frozenset({"ppermute", "pshuffle"})

HALF_DTYPES = ("float16", "bfloat16")

#: kinds compiled under the donation policy whose lowered HLO must
#: alias at least one input buffer to an output
DONATED_KINDS = frozenset({"fused_adam", "fused_sgd", "train_step"})


# ---------------------------------------------------------------------------
# result model
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Check:
    name: str
    ok: bool
    detail: str = ""


@dataclasses.dataclass
class ProgramReport:
    name: str                      # display name, e.g. "train_step[telemetry]"
    kind: str                      # step_cache kind or "kernel.<name>.<tier>"
    checks: List[Check] = dataclasses.field(default_factory=list)

    @property
    def passed(self) -> bool:
        return all(c.ok for c in self.checks)


class AuditResult:
    def __init__(self):
        self.programs: List[ProgramReport] = []
        self.errors: List[str] = []
        self.elapsed_ms: float = 0.0

    @property
    def passed(self) -> bool:
        return not self.errors and all(p.passed for p in self.programs)

    def failures(self) -> List[Tuple[str, Check]]:
        return [(p.name, c) for p in self.programs for c in p.checks
                if not c.ok]

    def counts(self) -> dict:
        return {
            "jaxpr_audit_ms": round(self.elapsed_ms, 1),
            "programs_audited": len(self.programs),
            "checks_run": sum(len(p.checks) for p in self.programs),
            "failures": len(self.failures()) + len(self.errors),
        }

    def format(self, verbose: bool = False) -> str:
        lines = []
        for p in self.programs:
            mark = "ok" if p.passed else "FAIL"
            lines.append(f"  [{mark:>4}] {p.name}  "
                         f"({len(p.checks)} checks)")
            for c in p.checks:
                if not c.ok:
                    lines.append(f"         - {c.name}: {c.detail}")
                elif verbose:
                    lines.append(f"         + {c.name}"
                                 + (f": {c.detail}" if c.detail else ""))
        for e in self.errors:
            lines.append(f"  [FAIL] audit error: {e}")
        n_fail = len(self.failures()) + len(self.errors)
        lines.append(
            f"jaxpr audit: {len(self.programs)} program(s), "
            f"{sum(len(p.checks) for p in self.programs)} check(s), "
            f"{n_fail} failure(s), ~{self.elapsed_ms / 1000.0:.1f}s")
        return "\n".join(lines)


# ---------------------------------------------------------------------------
# jaxpr walking
# ---------------------------------------------------------------------------


def _sub_jaxprs(params: dict):
    """Inner jaxprs of one eqn's params, whatever the spelling
    (pjit's ``jaxpr``, scan/while's ``jaxpr``/``cond_jaxpr``/
    ``body_jaxpr``, cond's ``branches``, custom_*'s callables are
    skipped — they retrace, they are not staged IR)."""
    for key, val in params.items():
        for item in (val if isinstance(val, (tuple, list)) else (val,)):
            jx = getattr(item, "jaxpr", None)
            if jx is not None and hasattr(jx, "eqns"):
                yield key, jx
            elif hasattr(item, "eqns"):
                yield key, item


def walk_eqns(jaxpr, scan_depth: int = 0):
    """Yield ``(eqn, scan_depth)`` over every eqn of ``jaxpr`` and its
    staged sub-jaxprs; ``scan_depth`` counts enclosing scan/while
    bodies."""
    for eqn in jaxpr.eqns:
        yield eqn, scan_depth
        is_loop = eqn.primitive.name in ("scan", "while")
        for _, sub in _sub_jaxprs(eqn.params):
            yield from walk_eqns(sub, scan_depth + (1 if is_loop else 0))


def iter_scans(jaxpr):
    """Yield every ``scan`` eqn in ``jaxpr`` (recursively)."""
    for eqn, _ in walk_eqns(jaxpr):
        if eqn.primitive.name == "scan":
            yield eqn


# ---------------------------------------------------------------------------
# the checks
# ---------------------------------------------------------------------------


def check_no_callbacks(jaxpr) -> Check:
    hits = sorted({eqn.primitive.name for eqn, _ in walk_eqns(jaxpr)
                   if eqn.primitive.name in CALLBACK_PRIMS})
    return Check(
        "no-callbacks", not hits,
        f"host callback primitive(s) staged into the program: {hits}"
        if hits else "no callback primitives")


def check_scan_collectives(jaxpr) -> Check:
    bad = sorted({eqn.primitive.name for eqn, depth in walk_eqns(jaxpr)
                  if depth > 0 and eqn.primitive.name in COLLECTIVE_PRIMS})
    return Check(
        "scan-collective", not bad,
        f"collective(s) inside a scan body: {bad} — hoist to the scan "
        f"boundary (accumulate locally, reduce once)"
        if bad else "collectives only at scan boundaries")


def check_scan_carries_fp32(jaxpr) -> Check:
    """No half-precision FLOATING carry in any scan: window accumulators
    must be fp32 (rng keys / ints / bools pass through untouched)."""
    bad = []
    n_scans = 0
    for eqn in iter_scans(jaxpr):
        n_scans += 1
        num_carry = eqn.params.get("num_carry", 0)
        inner = eqn.params["jaxpr"].jaxpr
        num_consts = eqn.params.get("num_consts", 0)
        carries = inner.invars[num_consts:num_consts + num_carry]
        for i, v in enumerate(carries):
            dt = str(getattr(v.aval, "dtype", ""))
            if dt in HALF_DTYPES:
                bad.append(f"carry[{i}]:{dt}{getattr(v.aval, 'shape', ())}")
    return Check(
        "scan-carry-fp32", not bad,
        f"half-precision scan carries (accumulate in fp32): {bad}"
        if bad else f"{n_scans} scan(s), all floating carries fp32")


def check_donation(entry) -> Check:
    """Lowered-HLO donation census: a program of a donated kind traced
    under ``donation.set(True)`` must alias inputs to outputs."""
    txt = entry["fn"].lower(*entry["example"]).as_text()
    n = txt.count("tf.aliasing_output")
    return Check(
        "donation-census", n >= 1,
        f"{n} aliased buffer(s)" if n else
        "no tf.aliasing_output in lowered HLO despite donation forced on")


def check_telemetry_carry(closed_off, closed_on, n_leaves: int) -> Check:
    """Telemetry grows the step by exactly its carry leaves, in == out:
    zero extra outputs beyond the carried accumulator itself."""
    d_in = len(closed_on.jaxpr.invars) - len(closed_off.jaxpr.invars)
    d_out = len(closed_on.jaxpr.outvars) - len(closed_off.jaxpr.outvars)
    ok = d_in == d_out == n_leaves
    return Check(
        "telemetry-carry", ok,
        f"telemetry=True delta: +{d_in} inputs / +{d_out} outputs "
        f"(expected +{n_leaves}/+{n_leaves}: the StepTelemetry leaves "
        f"ride the state carry, nothing else)" if not ok else
        f"+{n_leaves} in / +{n_leaves} out, zero extra")


# ---------------------------------------------------------------------------
# program providers (tiny real workloads, CPU)
# ---------------------------------------------------------------------------


def _entries_after(n0: int):
    from apex_tpu.runtime import step_cache as sc
    return sc.step_cache.entries()[n0:]


def _n_entries() -> int:
    from apex_tpu.runtime import step_cache as sc
    return len(sc.step_cache.entries())


def _train_workload(telemetry: bool):
    """One optimizer window of the fused train step: 2-microbatch grad
    accumulation (so the program HAS a scan) in fp16 AMP."""
    import jax.numpy as jnp
    import numpy as np

    import apex_tpu.nn as nn
    from apex_tpu.nn import functional as F
    from apex_tpu.optimizers import FusedSGD
    from apex_tpu.training import make_train_step

    nn.manual_seed(11)
    model = nn.Sequential(nn.Linear(6, 5), nn.ReLU(), nn.Linear(5, 3))
    opt = FusedSGD(list(model.parameters()), lr=0.05, momentum=0.9)
    step = make_train_step(model, opt,
                           lambda o, y: F.cross_entropy(o, y),
                           half_dtype=jnp.float16,
                           grad_accum_steps=2,
                           telemetry=telemetry)
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((4, 6)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 3, size=(4,)), jnp.int32)
    step(x, y)


def _optimizer_workload():
    """The eager executor surface: FusedAdam.step() over two parameter
    shapes (the test_executor donation-census workload, miniaturized)."""
    import jax.numpy as jnp
    import numpy as np

    from apex_tpu.nn import Parameter
    from apex_tpu.optimizers import FusedAdam

    rng = np.random.default_rng(5)
    params = []
    for s in [(9,), (4, 3)]:
        p = Parameter(jnp.asarray(rng.standard_normal(s), jnp.float32))
        p.grad = jnp.asarray(rng.standard_normal(s), jnp.float32)
        params.append(p)
    opt = FusedAdam(params, lr=1e-2)
    opt.step()


def _serve_workload():
    """Prefill + decode through the continuous-batching engine on a
    2-layer toy LM — populates the prefill_step/decode_step kinds.
    Returns the engine: its programs leave the step cache with it."""
    import apex_tpu.nn as nn
    from apex_tpu.models.gpt import GptModel
    from apex_tpu.serve import Request, ServeEngine

    nn.manual_seed(13)
    model = GptModel(vocab_size=41, hidden=24, layers=1, heads=2,
                     max_positions=48, dropout=0.0, attn_dropout=0.0)
    model.eval()
    eng = ServeEngine(model, num_blocks=24, block_size=4, max_batch=2)
    eng.run([Request("a", [3, 7, 5], 3), Request("b", [9, 2], 3)])
    return eng


def _trace_entry(entry):
    import jax
    return jax.make_jaxpr(lambda *a: entry["fn"](*a))(*entry["example"])


def _audit_entry(entry, *, name=None, donated=False,
                 scan_carries=False) -> ProgramReport:
    rep = ProgramReport(name=name or entry["kind"], kind=entry["kind"])
    try:
        closed = _trace_entry(entry)
    except Exception as exc:           # noqa: BLE001 — report, don't crash
        rep.checks.append(Check("trace", False,
                                f"{type(exc).__name__}: {exc}"))
        return rep
    rep.checks.append(check_no_callbacks(closed.jaxpr))
    rep.checks.append(check_scan_collectives(closed.jaxpr))
    if scan_carries:
        rep.checks.append(check_scan_carries_fp32(closed.jaxpr))
    if donated:
        try:
            rep.checks.append(check_donation(entry))
        except Exception as exc:       # noqa: BLE001
            rep.checks.append(Check("donation-census", False,
                                    f"{type(exc).__name__}: {exc}"))
    return rep


def _kernel_reports() -> List[ProgramReport]:
    """Both tiers of every registered kernel, traced abstractly from the
    spec's ``audit_programs`` hook (tier label, callable, example
    avals)."""
    import jax

    import apex_tpu.kernels  # noqa: F401 — registration side effects
    from apex_tpu.kernels.dispatch import catalog

    out = []
    for kname in sorted(catalog()):
        spec = catalog()[kname]
        hook = getattr(spec, "audit_programs", None)
        rep_name = f"kernel.{kname}"
        if hook is None:
            rep = ProgramReport(name=rep_name, kind=rep_name)
            rep.checks.append(Check(
                "audit-hook", False,
                "registered kernel declares no audit_programs hook — "
                "both tiers must be traceable by the verifier"))
            out.append(rep)
            continue
        tiers = set()
        for tier, fn, example in hook():
            tiers.add(tier)
            rep = ProgramReport(name=f"{rep_name}.{tier}",
                                kind=f"kernel.{kname}.{tier}")
            try:
                closed = jax.make_jaxpr(fn)(*example)
            except Exception as exc:   # noqa: BLE001
                rep.checks.append(Check("trace", False,
                                        f"{type(exc).__name__}: {exc}"))
                out.append(rep)
                continue
            rep.checks.append(check_no_callbacks(closed.jaxpr))
            rep.checks.append(check_scan_collectives(closed.jaxpr))
            out.append(rep)
        if not {"pallas", "xla"} <= tiers:
            rep = ProgramReport(name=rep_name, kind=rep_name)
            rep.checks.append(Check(
                "both-tiers", False,
                f"audit hook covers tiers {sorted(tiers)}; need both "
                f"'pallas' and 'xla'"))
            out.append(rep)
    return out


# ---------------------------------------------------------------------------
# the driver
# ---------------------------------------------------------------------------

_RESULT: Optional[AuditResult] = None


def run(force: bool = False) -> AuditResult:
    """Collect and audit every entry program; memoized per process."""
    global _RESULT
    if _RESULT is not None and not force:
        return _RESULT
    res = AuditResult()
    t0 = time.perf_counter()
    try:
        _run_into(res)
    except Exception as exc:           # noqa: BLE001 — an audit that
        # cannot even set up is a failing audit, not a crash of the CLI
        res.errors.append(f"{type(exc).__name__}: {exc}")
    res.elapsed_ms = (time.perf_counter() - t0) * 1000.0
    _RESULT = res
    return res


def _run_into(res: AuditResult) -> None:
    import jax

    from apex_tpu.observe.telemetry import init_telemetry
    from apex_tpu.runtime import executor as rex

    # train + eager-optimizer programs trace under forced donation so
    # the census sees the aliasing the accelerator path compiles with
    rex.donation.set(True)
    try:
        n0 = _n_entries()
        _train_workload(telemetry=False)
        train_off = _entries_after(n0)

        n1 = _n_entries()
        _train_workload(telemetry=True)
        train_on = _entries_after(n1)

        n2 = _n_entries()
        _optimizer_workload()
        opt_entries = _entries_after(n2)
    finally:
        rex.donation.set("auto")

    n3 = _n_entries()
    serve_engine = _serve_workload()
    serve_entries = _entries_after(n3)
    del serve_engine

    for e in train_off:
        res.programs.append(_audit_entry(
            e, donated=e["kind"] in DONATED_KINDS, scan_carries=True))
    for e in train_on:
        res.programs.append(_audit_entry(
            e, name=f"{e['kind']}[telemetry]",
            donated=e["kind"] in DONATED_KINDS, scan_carries=True))
    for e in opt_entries:
        res.programs.append(_audit_entry(
            e, donated=e["kind"] in DONATED_KINDS))
    for e in serve_entries:
        res.programs.append(_audit_entry(e))

    # telemetry-carry: the two train_step programs, off vs on
    base = [e for e in train_off if e["kind"] == "train_step"]
    tele = [e for e in train_on if e["kind"] == "train_step"]
    rep = ProgramReport(name="train_step[telemetry-delta]",
                        kind="train_step")
    if len(base) == 1 and len(tele) == 1:
        n_leaves = len(jax.tree_util.tree_leaves(init_telemetry()))
        try:
            rep.checks.append(check_telemetry_carry(
                _trace_entry(base[0]), _trace_entry(tele[0]), n_leaves))
        except Exception as exc:       # noqa: BLE001
            rep.checks.append(Check("telemetry-carry", False,
                                    f"{type(exc).__name__}: {exc}"))
    else:
        rep.checks.append(Check(
            "telemetry-carry", False,
            f"expected exactly one train_step per telemetry mode, got "
            f"{len(base)} off / {len(tele)} on"))
    res.programs.append(rep)

    res.programs.extend(_kernel_reports())
