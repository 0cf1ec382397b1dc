"""Readers of the device's idle time, split by what the host was doing.

Each serve tick owns the time from the start of its ``serve.step`` record
to the start of the next one.  Every idle nanosecond of the first
device's ``XLA Ops`` line in that time goes to the innermost program
record open on the engine's thread at that instant, by interval and not
by midpoint: ``dispatch.decode_step``, ``serve.fetch.tokens``,
``serve.commit``, ``serve.pack``, ``host.gc``, ``serve.step self`` (no
child open), and, between the end of one ``serve.step`` and the start of
the next, ``outside the engine`` (the harness's own work).

A root record's ``clock_ns`` puts it and every record below it on the
profiler's host clock (``apex_tpu/observe/spans.py``), read once a tick,
so the two clocks cannot drift apart over a window.  The device trace
counts from its session's start on that clock, and its device plane
stamps a program earlier than its host plane does, by an offset drawn
afresh each run (0.35-1.6 ms on a v5e: PERF.md section 5):
:func:`trace_zero` needs both from the trace, which the harness's
reduction does not keep yet (``tools/idle_split.py`` reads them from
the trace file).  A program that keeps no ``clock_ns`` (a
parent commit), a trace without them or without operations, or a window
without a decode tick gives every reader here nothing to read.
The first reader called prints one line: the window's idle seconds by
label, and their sum against the device's idle time between the
window's first and last tick.
"""
import bisect
import os

from pb import cells
from pb import trace as _trace
from pb.runenv import percentile

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
OUTSIDE = "outside the engine"
SELF = "serve.step self"


def _spans():
    return cells._module_at(_REPO, "readers", "spans")


class Busy:
    """The union of the device's operation intervals, with the busy time
    before each point at hand."""

    def __init__(self, ops):
        merged = _trace.union((s, s + d) for _, s, d in ops if d > 0)
        self.starts = [s for s, _ in merged]
        self.ends = [e for _, e in merged]
        self.before = [0.0]
        for s, e in merged:
            self.before.append(self.before[-1] + (e - s))

    def _upto(self, t) -> float:
        i = bisect.bisect_right(self.starts, t)
        if i == 0:
            return 0.0
        return self.before[i - 1] + min(t, self.ends[i - 1]) \
            - self.starts[i - 1]

    def idle(self, lo, hi) -> float:
        """Nanoseconds of ``[lo, hi)`` in which no operation ran."""
        if hi <= lo:
            return 0.0
        return (hi - lo) - (self._upto(hi) - self._upto(lo))


def _label(rec) -> str:
    return SELF if rec["span"] == "serve.step" else _spans()._label(rec)


def pieces(rec, kids, lo, hi, out) -> list:
    """``[lo, hi)`` of ``rec`` cut into ``(t0, t1, label)`` by the
    innermost record open at each instant; a child is clipped to its
    parent and to the child before it, so the pieces tile the interval."""
    cur = lo
    for k in kids.get(rec["id"], []):
        a, b = max(k["t0_ns"], cur), min(k["t1_ns"], hi)
        if b <= a:
            continue
        if a > cur:
            out.append((cur, a, _label(rec)))
        pieces(k, kids, a, b, out)
        cur = b
    if hi > cur:
        out.append((cur, hi, _label(rec)))
    return out


def window_steps(ctx) -> list:
    """The ``serve.step`` records from the window's first tick to its
    last, in order, each with ``clock_ns``; [] where the program keeps no
    clock."""
    ticks = ctx["counters"].get("ticks") or []
    if not ticks:
        return []
    lo, hi = ticks[0]["t0"] * 1e9, ticks[-1]["t1"] * 1e9
    steps = [r for r in _spans()._records(ctx)
             if r["span"] == "serve.step" and lo <= r["t0_ns"]
             and r["t1_ns"] <= hi]
    if not steps or any("clock_ns" not in r for r in steps):
        return []
    return steps


def trace_zero(tr):
    """Where the device trace's zero lies on the profiler's host clock:
    the session's start (``start_ns``, the trace's ``profile_start_time``)
    plus how much earlier the device plane stamps an event than the host
    plane (``device_offset_ns``, read from the runtime's own host events
    by ``tools/idle_split.py``); None where the trace keeps either not."""
    if tr.get("start_ns") is None or tr.get("device_offset_ns") is None:
        return None
    return float(tr["start_ns"]) + float(tr["device_offset_ns"])


def split(ctx):
    """``{"ticks": [(serve.step record, {label: idle ns})], "labels":
    {label: idle ns}, "idle_ns", "span_ns"}`` over the window, read
    once a run; None where nothing can be read."""
    if "idle_split" in ctx:
        return ctx["idle_split"]
    ctx["idle_split"] = None
    tr = ctx.get("trace")
    if not tr or not tr.get("ops") or trace_zero(tr) is None:
        return None
    steps = window_steps(ctx)
    if not steps:
        return None
    zero = trace_zero(tr)
    kids = ctx["span_children"]
    busy = Busy(tr["ops"])
    out, labels = [], {}
    for n, r in enumerate(steps):
        shift = r["clock_ns"] - zero
        mine = {}
        for a, b, label in pieces(r, kids, r["t0_ns"], r["t1_ns"], []):
            mine[label] = mine.get(label, 0.0) \
                + busy.idle(a + shift, b + shift)
        if n + 1 < len(steps):
            nxt = steps[n + 1]
            mine[OUTSIDE] = busy.idle(
                r["t1_ns"] + shift, nxt["t0_ns"] + nxt["clock_ns"] - zero)
        for label, ns in mine.items():
            labels[label] = labels.get(label, 0.0) + ns
        out.append((r, mine))
    first, last = steps[0], steps[-1]
    lo = first["t0_ns"] + first["clock_ns"] - zero
    hi = last["t1_ns"] + last["clock_ns"] - zero
    ctx["idle_split"] = {"ticks": out, "labels": labels,
                         "idle_ns": busy.idle(lo, hi), "span_ns": hi - lo}
    _report(ctx["idle_split"])
    return ctx["idle_split"]


#: which labels each metric reads
PARTS = {
    "dispatch": lambda label: label.startswith("dispatch"),
    "fetch": lambda label: label.startswith("serve.fetch"),
    "host": lambda label: not label.startswith(("dispatch", "serve.fetch"))
    and label != OUTSIDE,
}


def decode_idle_ms(ctx, part):
    """Median over the window's ticks that dispatched ``decode_step`` of
    the device's idle time while the host was inside ``part``:
    ``dispatch`` (a ``dispatch`` record), ``fetch`` (``serve.fetch``) or
    ``host`` (the rest of ``serve.step``: pack, commit, admit,
    ensure_blocks, its self time, ``host.gc``)."""
    sp = split(ctx)
    if sp is None:
        return None
    decode = {r["id"] for r in _spans().window_ticks(ctx, "decode_step")}
    wants = PARTS[part]
    per = [sum(ns for label, ns in mine.items() if wants(label))
           for r, mine in sp["ticks"] if r["id"] in decode]
    if not per:
        return None
    return percentile(per, 50) / 1e6


def _report(sp) -> None:
    total = sum(sp["labels"].values())
    parts = ", ".join(f"{label} {ns / 1e9:.4f}" for label, ns in
                      sorted(sp["labels"].items(), key=lambda kv: -kv[1]))
    print(f"[perfbench idle] device idle by what the host was doing, "
          f"{len(sp['ticks'])} ticks, {sp['span_ns'] / 1e9:.4f} s from the "
          f"window's first tick to its last, idle {sp['idle_ns'] / 1e9:.4f} "
          f"s: {parts} s; the labels sum to {total / 1e9:.4f} s, "
          f"{100.0 * total / sp['idle_ns'] if sp['idle_ns'] else 0.0:.2f}% "
          f"of it", flush=True)
