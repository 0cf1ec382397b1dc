"""apex_tpu.pyprof — profiling & op-level performance analysis.

TPU re-design of apex/pyprof (4981 LoC; SURVEY.md §3.5/§5).  The reference's
three-process pipeline — (1) NVTX-annotated run under nvprof, (2)
``python -m apex.pyprof.parse`` joining kernels to markers from the SQL
dump, (3) ``python -m apex.pyprof.prof`` applying per-op FLOP/byte models —
maps onto XLA's trace-once model as:

1. ``pyprof.nvtx.init()`` + ``pyprof.capture()`` — annotate
   apex_tpu.nn.functional at trace time (annotate.py); each op records
   shapes/dtypes/params/callsite once per compiled trace and tags the HLO
   with ``jax.named_scope`` so ``jax.profiler`` traces carry the same
   labels (no SQL join needed — the correlation the reference reconstructs
   from seq ids ships inside the HLO metadata).
2. ``python -m apex_tpu.pyprof.parse run.jsonl > net.dict`` — enrich the
   raw event log: stable seq ids, synthesized backward ops per autograd
   rules (the reference recovers bwd kernels from nvprof; under jax.grad
   the backward is derivable from the forward trace).
3. ``python -m apex_tpu.pyprof.prof net.dict`` — per-op FLOPs / bytes /
   arithmetic intensity / MXU-eligibility models and a roofline time
   estimate (prof/models.py), columnar or CSV output.

Programmatic one-shot: ``pyprof.analyze(events)`` → list of measured rows.
"""
from __future__ import annotations

import contextlib
import json

from . import annotate
from . import nvtx  # noqa: F401


@contextlib.contextmanager
def capture(clear: bool = True):
    """Enable recording for a scope; yields the (live) event list."""
    annotate.init()
    if clear:
        annotate.clear()
    annotate.set_enabled(True)
    try:
        yield annotate.events()
    finally:
        annotate.set_enabled(False)


def save(path: str, events=None):
    """Write captured events as JSON lines (the 'nvprof sql dump' stand-in
    consumed by ``python -m apex_tpu.pyprof.parse``)."""
    events = events if events is not None else annotate.events()
    with open(path, "w") as f:
        for e in events:
            f.write(json.dumps(e) + "\n")
    return path


def profile_step(fn, *args, trace_dir=None, executions: int = 3,
                 with_backward: bool = True, analyze_output: bool = True):
    """Measured profile of a jittable step: annotate, compile, execute under
    ``jax.profiler.trace``, join thunk timings to ops through the HLO
    metadata (parse/trace.py), and run the prof-stage models.

    Returns ``(rows, report)``: rows carry both the analytic columns
    (flops/bytes/roofline est_us) and measured ``meas_us``/achieved TFLOP/s;
    report holds the join statistics."""
    from .parse.trace import profile_step as _ps
    rows, report = _ps(fn, *args, trace_dir=trace_dir,
                       executions=executions, with_backward=with_backward)
    if analyze_output:
        from .prof.prof import analyze_rows
        rows = analyze_rows(rows)
    return rows, report


def analyze(events=None, with_backward: bool = True):
    """events → analyzed rows (parse + prof stages fused, in process)."""
    from .parse.parse import enrich
    from .prof.prof import analyze_rows
    events = events if events is not None else annotate.events()
    return analyze_rows(enrich(events, with_backward=with_backward))


_thunk_capability = None


def thunk_events_available() -> bool:
    """One-shot runtime probe: does ``jax.profiler.trace`` on THIS
    backend/jaxlib emit per-thunk duration events?

    The CPU backend writes the trace plugin's metadata but no thunk
    timings, which left the measured-profile pipeline dead behind two
    xfail'd tests.  The probe runs one trivial jitted function under a
    trace into a tempdir and checks whether ``parse.trace`` can extract
    any duration-carrying thunk events — callers (and the test suite)
    gate the measured path on the answer instead of guessing from
    platform names.  Result is cached for the process; any probe failure
    (no profiler, no writable tmp) counts as "not available".
    """
    global _thunk_capability
    if _thunk_capability is None:
        _thunk_capability = _probe_thunk_events()
    return _thunk_capability


def _probe_thunk_events() -> bool:
    import tempfile

    from .parse.trace import find_trace_json, load_thunk_events
    try:
        import jax
        import jax.numpy as jnp

        @jax.jit
        def _probe(x):
            return (x * x).sum()

        _probe(jnp.ones((8, 8))).block_until_ready()
        with tempfile.TemporaryDirectory() as d:
            with jax.profiler.trace(d):
                _probe(jnp.ones((8, 8))).block_until_ready()
            # find_trace_json raises FileNotFoundError when the trace
            # plugin wrote nothing — caught below as "not available"
            thunks = load_thunk_events(find_trace_json(d))
            return any(t.get("dur_us", 0) > 0 for t in thunks)
    except Exception:
        return False


__all__ = ["annotate", "nvtx", "capture", "save", "analyze",
           "thunk_events_available"]
