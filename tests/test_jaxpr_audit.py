"""The jaxpr-level program verifier (apex_tpu.lint.jaxpr_audit): the
tier-1 gate (every real entry program passes every IR check), the
cross-checks grounding its verdicts in ``step_cache.kind_stats`` and
the lowered HLO, and the ``--jaxpr`` CLI surface."""
import subprocess
import sys

import pytest

from apex_tpu.lint import jaxpr_audit
from apex_tpu.runtime import step_cache as sc

pytestmark = pytest.mark.lint


@pytest.fixture(scope="module")
def audit():
    """One audited run for the whole module, with stats reset first so
    kind_stats cross-checks count exactly the audit's own workloads, and
    the cache emptied: the audit slices the cache's entries by index, so
    a worker that ran other files first and holds the LRU at its cap
    would hand it empty slices."""
    sc.reset_stats()
    sc.clear()
    return jaxpr_audit.run(force=True)


def _report(audit, name):
    (rep,) = [p for p in audit.programs if p.name == name]
    return rep


def _check(rep, name):
    (c,) = [c for c in rep.checks if c.name == name]
    return c


# ---------------------------------------------------------------------------
# the tier-1 gate
# ---------------------------------------------------------------------------


def test_every_audited_program_passes(audit):
    assert audit.programs, "audit collected no programs"
    assert audit.passed, "\n" + audit.format()


def test_audit_covers_the_entry_surfaces(audit):
    kinds = {p.kind for p in audit.programs}
    # train, eager optimizer, serve — the three executor surfaces
    assert "train_step" in kinds
    assert "fused_adam" in kinds
    assert "prefill_step" in kinds and "decode_step" in kinds
    # every registered kernel, both tiers
    from apex_tpu.kernels.dispatch import catalog
    for kname in catalog():
        assert f"kernel.{kname}.pallas" in kinds, kname
        assert f"kernel.{kname}.xla" in kinds, kname


def test_audit_counts_schema(audit):
    c = audit.counts()
    assert {"jaxpr_audit_ms", "programs_audited", "checks_run",
            "failures"} <= set(c)
    assert c["programs_audited"] == len(audit.programs) >= 12
    assert c["failures"] == 0


def test_telemetry_carry_delta_is_exact(audit):
    rep = _report(audit, "train_step[telemetry-delta]")
    assert rep.passed, rep.checks
    assert "+5 in / +5 out" in _check(rep, "telemetry-carry").detail


# ---------------------------------------------------------------------------
# cross-checks: the IR verdicts against runtime counters and lowered HLO
# ---------------------------------------------------------------------------


def test_train_verdict_matches_kind_stats(audit):
    """The two audited train_step programs (telemetry off/on) are the
    two compiles the runtime counted — the audit judged the programs
    that actually executed, not a parallel reconstruction."""
    assert audit.passed
    stats = sc.kind_stats("train_step")
    assert stats["compiles"] == 2
    assert stats["dispatches"] == 2
    # and the audited program really contains the 2-microbatch window:
    rep = _report(audit, "train_step")
    detail = _check(rep, "scan-carry-fp32").detail
    n_scans = int(detail.split(" ")[0])
    assert n_scans >= 1


def test_serve_verdict_matches_kind_stats(audit):
    """The serve programs the audit passed are the ones the engine
    dispatched: decode compiled at least once and re-dispatched per
    generated token without a callback in sight."""
    assert audit.passed
    decode = sc.kind_stats("decode_step")
    assert decode["compiles"] >= 1
    assert decode["dispatches"] >= decode["compiles"]
    assert sc.kind_stats("prefill_step")["compiles"] >= 1


def test_donation_census_matches_executor_hlo_bound(audit):
    """Generalization stays anchored to the original bound
    (test_executor.py::test_donation_alias_in_lowered_hlo): FusedAdam
    over 2 params donates params + exp_avg + exp_avg_sq per bucket plus
    the step counter — at least 7 aliased buffers in the HLO."""
    rep = _report(audit, "fused_adam")
    c = _check(rep, "donation-census")
    assert c.ok
    n = int(c.detail.split(" ")[0])
    assert n >= 3 * 2 + 1


# ---------------------------------------------------------------------------
# the CLI surface
# ---------------------------------------------------------------------------


def test_cli_jaxpr_exits_zero_on_shipped_tree(audit, capsys):
    """The acceptance-spelled invocation, in-process against the
    memoized audit (the subprocess spelling re-traces every program —
    ~40s of pure import/trace repeat — so it rides the slow tier)."""
    from apex_tpu.lint.__main__ import main as lint_main

    rc = lint_main(["--jaxpr"])
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "0 failure(s)" in out


@pytest.mark.slow
def test_cli_jaxpr_subprocess_exits_zero():
    proc = subprocess.run(
        [sys.executable, "-m", "apex_tpu.lint", "--jaxpr"],
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "0 failure(s)" in proc.stdout


# ---------------------------------------------------------------------------
# dynamic oracle: PRECISION-SINK's static verdict vs fp16 arithmetic
# ---------------------------------------------------------------------------


def test_precision_sink_dynamic_oracle():
    """The flagged fixture really overflows: an fp16-accumulated energy
    sum saturates to inf on values whose fp32 twin is ~66k, while the
    fp32-reduction fixture stays finite on the SAME input."""
    import importlib
    import os

    import jax.numpy as jnp
    import numpy as np

    from apex_tpu import lint as tpu_lint

    fixtures = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "lint_fixtures")
    bad_path = os.path.join(fixtures, "oracle_precision_bad.py")
    good_path = os.path.join(fixtures, "oracle_precision_good.py")

    bad_res = tpu_lint.run([bad_path], select=["PRECISION-SINK"],
                           baseline=None)
    good_res = tpu_lint.run([good_path], select=["PRECISION-SINK"],
                            baseline=None)
    assert len(bad_res.active()) == 1          # static verdict: flagged
    assert not good_res.active()               # static verdict: clean

    sys.path.insert(0, fixtures)
    try:
        bad = importlib.import_module("oracle_precision_bad")
        good = importlib.import_module("oracle_precision_good")
    finally:
        sys.path.pop(0)
    xs = jnp.full((4096,), 4.0, jnp.float32)   # energy = 16 * 4096 = 65536
    assert np.isinf(np.asarray(bad.window_energy(xs)))       # > fp16 max
    assert np.isfinite(np.asarray(good.window_energy(xs)))
    np.testing.assert_allclose(np.asarray(good.window_energy(xs)),
                               65536.0, rtol=1e-3)
