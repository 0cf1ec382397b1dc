"""Measured-profile ingestion: join ``jax.profiler`` trace events against the
annotate-stage op log.

The reference's parse stage reads an nvprof SQL database and correlates GPU
kernel rows to the NVTX marker ranges that enclose them, using autograd
seq-ids for forward<->backward correlation
(/root/reference/apex/pyprof/parse/nvvp.py:91-199).  The TPU-native
equivalent has three measured inputs:

1. the annotate op log (trace-time shapes/dtypes, one ``ppN_<op>`` named
   scope per event — annotate.py);
2. the compiled program's HLO text, whose per-instruction
   ``metadata={op_name="jit(f)/jvp(ppN_op)/..."}`` carries those scopes
   through XLA's optimizer (fusion instructions keep their root's metadata);
3. a ``jax.profiler.trace`` dump, whose device/runtime lanes carry one
   complete event per executed thunk/kernel, named by HLO instruction.

The join is therefore: thunk event name -> HLO instruction -> metadata
op_name -> ``ppN`` seq id, with direction read off the ``transpose(...)``
wrapper jax puts around reverse-mode ops — the seq-id correlation of
nvvp.py:149-173 expressed in XLA metadata instead of an SQL table.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re

# host-runtime bookkeeping events on the device lanes that are not kernels
_INFRA = ("ThreadpoolListener", "ThunkExecutor", "end: ")
# whole-program span events: "jit_step(2360695404505296586)" etc.
_PROGRAM_RE = re.compile(r"^jit_?[\w$.\-]*\(-?\d+\)$")

_SCOPE_RE = re.compile(r"pp(\d+)_")
_INSTR_RE = re.compile(
    r"%?([\w.\-]+)\s*=\s*[^\n]*metadata=\{[^}]*op_name=\"([^\"]+)\"")


def find_trace_json(path: str) -> str:
    """Locate the ``*.trace.json.gz`` under a ``jax.profiler.trace`` output
    directory (``<dir>/plugins/profile/<run>/<host>.trace.json.gz``), or
    pass a direct file path through."""
    if os.path.isfile(path):
        return path
    hits = sorted(glob.glob(
        os.path.join(path, "**", "*.trace.json*"), recursive=True))
    if not hits:
        raise FileNotFoundError(
            f"no *.trace.json(.gz) found under {path!r}; pass the directory "
            f"given to jax.profiler.trace()")
    return hits[-1]  # newest run


def load_thunk_events(path: str):
    """All complete ("ph":"X") events from the trace's device/runtime lanes
    as ``{"name", "dur_us", "ts_us"}`` dicts.

    Lane selection: anything that is NOT the python host thread — TPU device
    processes are named "/device:TPU:N", the CPU backend's thunk executor
    thread "tf_XLAPjRtCpuClient/..."; python host events are prefixed "$" or
    carry python frame names and live on the thread named "python".
    """
    f = find_trace_json(path)
    opener = gzip.open if f.endswith(".gz") else open
    with opener(f, "rt") as fh:
        data = json.load(fh)
    events = data.get("traceEvents", [])

    # lane selection is positive, not negative: only device-process lanes
    # ("/device:TPU:N" on hardware) and the CPU backend's thunk-executor
    # thread count as kernel lanes.  Host TraceMe spans (PjRt execute /
    # transfer bookkeeping on arbitrary threads) would otherwise inflate
    # the unattributed total and make the join statistic meaningless.
    proc_names = {}
    thread_names = {}
    for e in events:
        if e.get("ph") != "M":
            continue
        if e.get("name") == "process_name":
            proc_names[e.get("pid")] = e.get("args", {}).get("name", "")
        elif e.get("name") == "thread_name":
            thread_names[(e.get("pid"), e.get("tid"))] = \
                e.get("args", {}).get("name", "")

    def is_kernel_lane(pid, tid):
        if proc_names.get(pid, "").startswith("/device:"):
            return True
        return "XLAPjRtCpuClient" in thread_names.get((pid, tid), "")

    out = []
    for e in events:
        if e.get("ph") != "X":
            continue
        if not is_kernel_lane(e.get("pid"), e.get("tid")):
            continue
        name = e.get("name", "")
        if name.startswith("$") or any(s in name for s in _INFRA):
            continue
        if _PROGRAM_RE.match(name) or name.isdigit():
            # whole-program umbrella spans on the device lane: named
            # "jit_step(<fingerprint>)" on one lane and by bare
            # per-execution run index ("0", "1", ...) on another — each
            # covers every thunk beneath it, so counting them
            # double-counts the entire execution as unattributed time
            # (round 4: 104ms of a 54ms resnet step)
            continue
        out.append({"name": name, "dur_us": float(e.get("dur", 0.0)),
                    "ts_us": float(e.get("ts", 0.0))})
    return out


def scope_map(hlo_text: str):
    """HLO instruction name -> metadata op_name path, for every instruction
    that carries one (fusions keep their root op's metadata, so fused
    kernels still attribute to an annotate scope)."""
    return {m.group(1): m.group(2)
            for m in _INSTR_RE.finditer(hlo_text)}


_THUNK_KIND_RE = re.compile(r"[A-Za-z_][\w\-]*?(?=[.\d]|$)")


def _thunk_kind(t, op_name):
    """Coarse category for an unattributed thunk: the HLO instruction-name
    stem ("fusion", "copy", "transpose", "convolution", "all-reduce", ...)
    or, when the instruction DID carry scope-less metadata, the last
    component of its op_name path prefixed "op:" — enough to tell layout
    transposes and copies apart from real compute in the unmatched bucket."""
    if op_name is not None:
        return "op:" + op_name.rsplit("/", 1)[-1]
    m = _THUNK_KIND_RE.match(t["name"].lstrip("%"))
    return m.group(0) if m else "other"


def correlate(thunks, smap):
    """-> (per-seq measurements, unattributed, unattributed_by) where
    measurements is ``{seq: {"fwd_us", "bwd_us", "fwd_n", "bwd_n"}}``
    summed over every execution captured in the trace and
    ``unattributed_by`` buckets the unmatched time by thunk category."""
    per_seq = {}
    unattributed_us = 0.0
    unattributed_by = {}

    def _miss(t, op_name):
        nonlocal unattributed_us
        unattributed_us += t["dur_us"]
        k = _thunk_kind(t, op_name)
        unattributed_by[k] = unattributed_by.get(k, 0.0) + t["dur_us"]

    for t in thunks:
        op_name = smap.get(t["name"])
        if op_name is None:
            _miss(t, None)
            continue
        m = _SCOPE_RE.search(op_name)
        if m is None:
            _miss(t, op_name)
            continue
        seq = int(m.group(1))
        d = per_seq.setdefault(
            seq, {"fwd_us": 0.0, "bwd_us": 0.0, "fwd_n": 0, "bwd_n": 0})
        if "transpose(" in op_name:
            d["bwd_us"] += t["dur_us"]
            d["bwd_n"] += 1
        else:
            d["fwd_us"] += t["dur_us"]
            d["fwd_n"] += 1
    return per_seq, unattributed_us, unattributed_by


def merge_measurements(rows, per_seq, executions: int = 1):
    """Attach measured per-execution durations to enriched rows (parse.py
    ``enrich`` output): fwd rows get ``dur_us`` from their seq's fwd sum,
    synthesized bwd rows from the bwd sum of the row they correlate to
    (``corr``).  Rows with no measurement keep ``dur_us=None`` (the analytic
    roofline estimate in the prof stage remains their only timing)."""
    n = max(1, executions)
    out = []
    for r in rows:
        r = dict(r)
        m = per_seq.get(r.get("corr", r.get("seq")))
        if m is None:
            r["dur_us"] = None
        elif r.get("dir") == "bwd":
            r["dur_us"] = round(m["bwd_us"] / n, 3) if m["bwd_n"] else None
        else:
            r["dur_us"] = round(m["fwd_us"] / n, 3) if m["fwd_n"] else None
        out.append(r)
    return out


def profile_step(fn, *args, trace_dir=None, executions: int = 3,
                 with_backward: bool = True):
    """One-stop measured profile of a jittable step: the TPU-native
    ``nvprof + parse`` run.

    Annotates ``fn``'s ops (annotate.init must have patched the op layer
    before ``fn``'s model/functional calls are bound), AOT-compiles it to
    capture the HLO metadata, executes it ``executions`` times under
    ``jax.profiler.trace``, and returns enriched rows carrying measured
    ``dur_us`` alongside the analytic columns.

    Returns ``(rows, report)`` where report carries the join statistics
    (matched/unmatched thunk time) — the visibility the reference gets from
    nvvp.py's per-kernel table.
    """
    import tempfile

    import jax

    from .. import annotate
    from .parse import enrich

    annotate.init()
    annotate.clear()
    annotate.set_enabled(True)
    try:
        jitted = jax.jit(fn)
        lowered = jitted.lower(*args)
    finally:
        annotate.set_enabled(False)
    events = [dict(e) for e in annotate.events()]
    compiled = lowered.compile()
    smap = scope_map(compiled.as_text())

    tmp = trace_dir or tempfile.mkdtemp(prefix="apex_tpu_pyprof_")
    try:
        with jax.profiler.trace(tmp):
            for _ in range(executions):
                out = compiled(*args)
            jax.block_until_ready(out)

        thunks = load_thunk_events(tmp)
    finally:
        if trace_dir is None:
            import shutil
            shutil.rmtree(tmp, ignore_errors=True)
            tmp = None
    per_seq, unattributed_us, unattributed_by = correlate(thunks, smap)
    rows = merge_measurements(
        enrich(events, with_backward=with_backward), per_seq,
        executions=executions)

    matched_us = sum(m["fwd_us"] + m["bwd_us"] for m in per_seq.values())
    report = {
        "trace_dir": tmp,
        "thunks": len(thunks),
        "matched_seqs": len(per_seq),
        "matched_us": round(matched_us, 3),
        "unattributed_us": round(unattributed_us, 3),
        "unattributed_by": {
            k: round(v, 3)
            for k, v in sorted(unattributed_by.items(),
                               key=lambda kv: -kv[1])},
        "executions": executions,
    }
    return rows, report
