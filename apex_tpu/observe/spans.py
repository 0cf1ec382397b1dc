"""Trace spans: one context manager, two outputs, one record.

``span("ckpt.save")`` emits (a) a structured ``span`` event into the
metrics registry and (b) a ``jax.profiler.TraceAnnotation`` so the same
region shows up in device profiles — host events and XLA timelines line
up by name.

The record is what a tree is built from: a process-unique ``id``, the
``parent`` (the span that was open *on the same thread* when this one
started, else None), ``t0_ns`` / ``t1_ns`` from
``time.perf_counter_ns()``, and the caller's fields (``kind``,
``tick``, ``rid``, ``step``, ``n``, ...).  A span opened inside one
that has a ``tick`` is of the same tick: it takes its parent's unless
it gives its own.  The annotation is named
``<name>.<kind>`` where the span has a ``kind`` and carries ``id`` (and
``tick``) as arguments, so a profiler host event joins to its record.
:func:`recorded` reads the records back.

A root record (``parent`` None) also carries ``clock_ns``: the
profiler's host clock (``time.time_ns()``, the realtime clock TSL
stamps its host events with) less ``time.perf_counter_ns()``, both read
beside its ``t0_ns``.  A time ``t`` of the root or of any record below
it is ``t + clock_ns`` on the profiler's clock; a trace file counts
from its session's ``profile_start_time`` on that clock.

A collector pause is a record too: ``host.gc``, with the collection's
``generation``, from one ``gc.callbacks`` hook installed on import.  Its
parent is the span open on the collecting thread (its ``tick`` is that
parent's), and it has an annotation like any span.  The hook takes no
lock: its records reach the registry at the next span's exit on any
thread, or at :func:`recorded`.

Spans are host-side instrumentation; entering one from jit-traced code
is a host round-trip and is flagged by the OBS-IN-JIT lint rule.
Thread-safe: the prefetch worker and async-checkpoint writer open spans
on their own threads (each thread has its own stack of open spans), and
the watchdog reads ``last_span()`` from its heartbeat thread.
"""
from __future__ import annotations

import collections
import contextlib
import gc
import itertools
import threading
import time
from typing import Any, Dict, List, Optional

from . import registry as _registry

#: ``span`` records kept in memory: about nine a serve tick and 60-150
#: ticks a second since the pool is no longer copied every tick, so two
#: to four minutes of serving (a benchmark window is 45 s, and its
#: readers look at the ring after the run)
SPAN_RING = 131072

_registry.get_registry().set_event_capacity("span", SPAN_RING)

_state_lock = threading.Lock()
_last_span: Optional[Dict[str, Any]] = None
_ids = itertools.count(1)           # next() is one bytecode: thread-safe
_open = threading.local()           # .stack: this thread's open records

_trace_annotation = None
_trace_annotation_probed = False

#: finished ``host.gc`` records not yet in the registry (appended from
#: the collector's hook, which must not take the registry's lock)
_gc_done: collections.deque = collections.deque()


def _get_trace_annotation():
    """Resolve jax.profiler.TraceAnnotation lazily; spans must work (as
    log-only) even when jax or its profiler is unavailable."""
    global _trace_annotation, _trace_annotation_probed
    if not _trace_annotation_probed:
        _trace_annotation_probed = True
        try:
            from jax import profiler as _profiler
            _trace_annotation = _profiler.TraceAnnotation
        except Exception:
            _trace_annotation = None
    return _trace_annotation


def last_span() -> Optional[Dict[str, Any]]:
    """Most recently *started* span (it may still be open) — the stall
    watchdog reports this as "where the runtime was last seen"."""
    with _state_lock:
        return dict(_last_span) if _last_span else None


def annotation_name(name: str, fields: Dict[str, Any]) -> str:
    """The span's name on the profiler's timeline: ``dispatch`` of kind
    ``decode_step`` is ``dispatch.decode_step``."""
    kind = fields.get("kind")
    return f"{name}.{kind}" if kind is not None else name


def _annotation(name: str, rec: Dict[str, Any]):
    """The profiler annotation of a record, not yet entered."""
    annotation = _get_trace_annotation()
    if annotation is None:
        return contextlib.nullcontext()
    args = {"id": rec["id"], "tick": rec["tick"]} if "tick" in rec \
        else {"id": rec["id"]}
    return annotation(name, **args)


def _flush_gc() -> None:
    while _gc_done:
        try:
            rec = _gc_done.popleft()
        except IndexError:          # another thread took the last one
            return
        _registry.event("span", **rec)


@contextlib.contextmanager
def span(name: str, **fields: Any):
    """Time a region; emit a ``span`` event on exit, wrapped in a
    profiler TraceAnnotation.

    Yields the record being built (the caller's fields, ``span``,
    ``id``, ``parent``, ``t0_ns``): the caller may add fields that
    are known only at the end (``rec["n_finished"] = 3``), and reads
    ``rec["dur_ms"]`` after the block."""
    global _last_span
    stack = getattr(_open, "stack", None)
    if stack is None:
        stack = _open.stack = []
    sid = next(_ids)
    parent = stack[-1] if stack else None
    # the record's own keys win over a caller's field of the same name
    rec = {**fields, "span": name, "id": sid,
           "parent": parent["id"] if parent else None}
    if parent and "tick" in parent:
        rec.setdefault("tick", parent["tick"])
    cm = _annotation(annotation_name(name, fields), rec)
    stack.append(rec)
    t0 = rec["t0_ns"] = time.perf_counter_ns()
    if parent is None:
        rec["clock_ns"] = time.time_ns() - t0
    with _state_lock:
        _last_span = {"span": name, "started_ms": t0 / 1e6, **fields}
    try:
        with cm:
            yield rec
    finally:
        t1 = rec["t1_ns"] = time.perf_counter_ns()
        stack.pop()
        rec["dur_ms"] = (t1 - t0) / 1e6
        _flush_gc()
        _registry.event("span", **rec)


def _on_gc(phase: str, info: Dict[str, Any]) -> None:
    """The ``gc.callbacks`` hook: a collection is a ``host.gc`` record
    below the span open on the collecting thread."""
    if phase == "start":
        stack = getattr(_open, "stack", None)
        parent = stack[-1] if stack else None
        rec = {"generation": info["generation"], "span": "host.gc",
               "id": next(_ids), "parent": parent["id"] if parent else None}
        if parent and "tick" in parent:
            rec["tick"] = parent["tick"]
        cm = _annotation("host.gc", rec)
        t0 = rec["t0_ns"] = time.perf_counter_ns()
        if parent is None:
            rec["clock_ns"] = time.time_ns() - t0
        cm.__enter__()
        _open.gc = rec, cm
        return
    rec, cm = _open.gc
    t1 = rec["t1_ns"] = time.perf_counter_ns()
    cm.__exit__(None, None, None)
    rec["dur_ms"] = (t1 - rec["t0_ns"]) / 1e6
    _gc_done.append(rec)


gc.callbacks.append(_on_gc)


def recorded(since_ns: Optional[int] = None) -> List[Dict[str, Any]]:
    """The ``span`` records in memory (the newest ``SPAN_RING``) that
    started at or after ``since_ns`` on the ``time.perf_counter_ns()``
    clock, oldest first by start: a parent comes before its children."""
    _flush_gc()
    recs = _registry.events("span")
    if since_ns is not None:
        recs = [r for r in recs if r["t0_ns"] >= since_ns]
    return sorted(recs, key=lambda r: r["t0_ns"])
