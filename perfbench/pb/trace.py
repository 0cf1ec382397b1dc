"""From a profiler trace to busy time, idle gaps, per-program device
time and exposed collective time.

The reduction works on plain tuples so that it can be checked on a small
recorded trace (``tests/perfbench/data``); :func:`load_xplane` is the thin
adapter from ``jax.profiler.ProfileData``.  What a TPU trace looks like
(TPU v5e, jax 0.9.0, looked at by hand in PR 25): one plane per chip,
``/device:TPU:<n>``, whose line ``XLA Modules`` has one event per program
execution (``jit_<fn>(<fingerprint>)``) and whose line ``XLA Ops`` has one
event per HLO operation, named by the operation's text; the plane
``/host:CPU`` has one line per thread; ``TraceAnnotation`` spans sit on
the line of the thread that made them, beside Python frames
(``$file:line fn``) where the Python tracer is on (the harness turns it
off).
"""
from __future__ import annotations

import bisect
import glob
import gzip
import json
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
COLLECTIVE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|collective-permute|"
    r"all-to-all)(-start|-done)?\b")
#: the operation's own name: "%fusion.12 = ..." -> "fusion.12"
_OP_NAME = re.compile(r"^%?([\w\-.]+)")
_TRAILING_NO = re.compile(r"[.\d]+$")


def newest_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load_xplane(path: str) -> dict:
    """``{"devices": {n: {"modules": [...], "ops": [...], "async": [...]}},
    "host": [...]}`` with events as ``(name, start_ns, dur_ns)``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = {"devices": {}, "host": []}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = {"modules": [], "ops": [], "async": []}
            for line in plane.lines:
                key = {"XLA Modules": "modules", "XLA Ops": "ops",
                       "Async XLA Ops": "async"}.get(line.name)
                if key is None:
                    continue
                dev[key] = [(e.name, e.start_ns, e.duration_ns)
                            for e in line.events]
            out["devices"][int(m.group(1))] = dev
        elif plane.name == "/host:CPU":
            # host spans of every thread: TraceAnnotations and the named
            # runtime calls, not Python frames ("$file:line fn")
            for line in plane.lines:
                out["host"] += [(e.name, e.start_ns, e.duration_ns)
                                for e in line.events
                                if not e.name.startswith("$")]
    return out


def save_recorded(trace: dict, path: str, max_ops=4000) -> None:
    """Write a trimmed trace as JSON (the recorded test fixture)."""
    small = {"devices": {}, "host": trace["host"][:max_ops]}
    for n, dev in trace["devices"].items():
        small["devices"][str(n)] = {
            k: [[_short(a), b, c] for a, b, c in v[:max_ops]]
            for k, v in dev.items()}
    with gzip.open(path, "wt") as f:
        json.dump(small, f)


def load_recorded(path: str) -> dict:
    with gzip.open(path, "rt") as f:
        raw = json.load(f)
    return {"devices": {int(n): {k: [tuple(e) for e in v]
                                 for k, v in dev.items()}
                        for n, dev in raw["devices"].items()},
            "host": [tuple(e) for e in raw["host"]]}


def _short(name: str) -> str:
    return name[:160]


def op_family(name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion``: operations
    grouped by their name without the trailing number."""
    m = _OP_NAME.match(name)
    base = m.group(1) if m else name
    return _TRAILING_NO.sub("", base) or base


def union(intervals) -> list:
    """Merged ``[start, end)`` intervals, ascending."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def total(merged) -> float:
    return float(sum(e - s for s, e in merged))


def subtract(a_merged, b_merged) -> list:
    """The part of ``a`` that no interval of ``b`` covers."""
    out = []
    j = 0
    for s, e in a_merged:
        cur = s
        while j < len(b_merged) and b_merged[j][1] <= cur:
            j += 1
        k = j
        while k < len(b_merged) and b_merged[k][0] < e:
            bs, be = b_merged[k]
            if bs > cur:
                out.append([cur, bs])
            cur = max(cur, be)
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def _ivals(events):
    return [(s, s + d) for _, s, d in events if d > 0]


def _is_collective(name: str) -> bool:
    m = _OP_NAME.match(name)
    return bool(COLLECTIVE.search(m.group(1) if m else name))


def reduce(trace: dict, window_s: float) -> dict:
    """Everything the per-layer readers take from a trace.

    ``busy_s``: the union of the intervals in which an operation ran,
    averaged over the device planes.  ``device_ops``: operation families
    by summed time (first device).  ``idle_gaps``: idle time of the first
    device attributed to the innermost host span that covers each gap's
    middle.  ``modules``: per-execution ``(name, start, dur)`` of the
    first device.  ``exposed_collective_s``: collective time during which
    no other operation ran on that device, averaged over devices."""
    devs = trace["devices"]
    if not devs:
        return {"busy_s": 0.0, "window_s": window_s, "n_devices": 0}
    busy, exposed, coll = [], [], []
    for dev in devs.values():
        ops = dev["ops"]
        merged = union(_ivals(ops))
        busy.append(total(merged) / 1e9)
        c_ops = [e for e in ops + dev.get("async", [])
                 if _is_collective(e[0])]
        compute = union(_ivals([e for e in ops if not _is_collective(e[0])]))
        c_merged = union(_ivals(c_ops))
        coll.append(total(c_merged) / 1e9)
        exposed.append(total(subtract(c_merged, compute)) / 1e9)
    first = devs[min(devs)]
    fam = {}
    for name, _, d in first["ops"]:
        k = op_family(name)
        fam[k] = fam.get(k, 0.0) + d / 1e9
    device_ops = sorted(fam.items(), key=lambda kv: -kv[1])
    merged = union(_ivals(first["ops"]))
    gaps = [(a[1], b[0]) for a, b in zip(merged, merged[1:])
            if b[0] > a[1]]
    by_span = {}
    host = sorted(trace["host"], key=lambda e: e[1])
    starts = [e[1] for e in host]
    for s, e in gaps:
        mid = (s + e) / 2.0
        i = bisect.bisect_right(starts, mid)
        name = "_no_host_span_"
        best = None
        # innermost span covering the middle: the latest-starting one
        for k in range(i - 1, max(-1, i - 200), -1):
            hn, hs, hd = host[k]
            if hs <= mid < hs + hd and (best is None or hd < best):
                name, best = hn, hd
        by_span[name] = by_span.get(name, 0.0) + (e - s) / 1e9
    idle_gaps = sorted(by_span.items(), key=lambda kv: -kv[1])
    return {
        "busy_s": sum(busy) / len(busy),
        "window_s": window_s,
        "n_devices": len(devs),
        "device_ops": [[re.sub(r"[^\w.\-]", "_", k), v]
                       for k, v in device_ops[:10]],
        "idle_gaps": [[re.sub(r"[^\w.\-:]", "_", k), v]
                      for k, v in idle_gaps[:10]],
        "modules": list(first["modules"]),
        "ops": first["ops"],
        "collective_s": sum(coll) / len(coll),
        "exposed_collective_s": sum(exposed) / len(exposed),
    }


def module_time_by_kind(modules, dispatch_log, name_prefixes):
    """Device seconds and executions per program kind.  ``dispatch_log``
    is the harness's list of kinds in dispatch order over the traced
    window; the trace's program executions whose name starts with one of
    ``name_prefixes`` are matched to it in time order.  Where the two
    disagree in length nothing can be attributed and None is returned."""
    mods = sorted((m for m in modules
                   if m[0].startswith(tuple(name_prefixes))),
                  key=lambda m: m[1])
    if not mods or len(mods) != len(dispatch_log):
        return None
    out = {}
    for (name, _, dur), kind in zip(mods, dispatch_log):
        t = out.setdefault(kind, {"seconds": 0.0, "n": 0})
        t["seconds"] += dur / 1e9
        t["n"] += 1
    return out


def time_by_kind(ctx):
    """:func:`module_time_by_kind` from a reader's context (the reduced
    trace and the harness's dispatch log), or None."""
    tr = ctx.get("trace")
    if not tr or not tr.get("modules"):
        return None
    c = ctx["counters"]
    return module_time_by_kind(tr["modules"], c["dispatch_log"],
                               c["module_prefixes"])
