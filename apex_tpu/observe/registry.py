"""Metrics registry + structured event log.

One process-global choke point for every number the runtime wants to
report: `step_cache.stats()` counters, checkpoint save/restore
latencies, chaos injections, elastic replan/reshard timings, planner
decisions, and the fused step's drained on-device telemetry all land
here instead of in per-subsystem private dicts.

Design constraints:

- **Thread-safe.** The prefetch worker, the async-checkpoint writer,
  and the stall watchdog all emit from their own threads.
- **Host-side only.** Nothing in this module may be called from
  jit-traced code (enforced by the OBS-IN-JIT lint rule) — every entry
  point touches a lock and Python containers, which inside a traced
  function would be a silent host round-trip at best.
- **Cheap.** A counter bump is a dict lookup + integer add under an
  RLock; no I/O unless a JSONL sink is attached.
- **Monotonic timestamps.** Event records carry `ts_ms` from
  `time.monotonic()` so ordering survives wall-clock steps (NTP slew
  on long runs); sinks that need wall time can add their own.
- **One ring per event name.** A frequent event (``span``: several a
  serve tick) cannot push a rare one (``watchdog.stall``,
  ``serve.request``) out of memory, and reading one name never scans
  another's records.
"""
from __future__ import annotations

import collections
import heapq
import json
import operator
import threading
import time
from typing import Any, Dict, List, Optional

SCHEMA_VERSION = 1

#: records kept in memory per event name, unless
#: :meth:`MetricsRegistry.set_event_capacity` asked for another bound
_EVENT_BUFFER_MAX = 4096


class Counter:
    """Monotonically increasing integer metric."""

    __slots__ = ("name", "_value", "_lock")

    def __init__(self, name: str):
        self.name = name
        self._value = 0
        self._lock = threading.Lock()

    def inc(self, n: int = 1) -> int:
        with self._lock:
            self._value += n
            return self._value

    @property
    def value(self) -> int:
        return self._value


class Gauge:
    """Last-write-wins scalar metric."""

    __slots__ = ("name", "_value")

    def __init__(self, name: str):
        self.name = name
        self._value: Optional[float] = None

    def set(self, value: float) -> None:
        self._value = float(value)

    @property
    def value(self) -> Optional[float]:
        return self._value


class Histogram:
    """Streaming summary: count / total / min / max / last.

    Full per-sample retention belongs in the event log (attach a JSONL
    sink); the in-memory histogram keeps O(1) state so hot paths like
    per-step latencies never grow memory.
    """

    __slots__ = ("name", "count", "total", "min", "max", "last", "_lock")

    def __init__(self, name: str):
        self.name = name
        self.count = 0
        self.total = 0.0
        self.min: Optional[float] = None
        self.max: Optional[float] = None
        self.last: Optional[float] = None
        self._lock = threading.Lock()

    def observe(self, value: float) -> None:
        value = float(value)
        with self._lock:
            self.count += 1
            self.total += value
            self.last = value
            self.min = value if self.min is None else min(self.min, value)
            self.max = value if self.max is None else max(self.max, value)

    @property
    def mean(self) -> Optional[float]:
        return self.total / self.count if self.count else None

    def summary(self) -> Dict[str, Any]:
        with self._lock:
            return {"count": self.count, "total": self.total,
                    "mean": self.mean, "min": self.min, "max": self.max,
                    "last": self.last}


class MetricsRegistry:
    """Named metrics plus a bounded structured event log.

    Events are dicts `{"schema": 1, "ts_ms": <monotonic ms>,
    "event": <name>, ...fields}`; the newest `_EVENT_BUFFER_MAX` *of
    each event name* are kept in memory (a ring per name) and every
    event is appended to any attached JSONL sinks as one line.
    """

    def __init__(self):
        self._lock = threading.RLock()
        self._counters: Dict[str, Counter] = {}
        self._gauges: Dict[str, Gauge] = {}
        self._histograms: Dict[str, Histogram] = {}
        #: event name -> ring of (arrival number, record)
        self._events: Dict[str, collections.deque] = {}
        self._capacity: Dict[str, int] = {}
        self._arrivals = 0
        self._sinks: Dict[str, Any] = {}   # path -> open file handle

    # -- metric accessors (create-on-first-use) ---------------------------

    def counter(self, name: str) -> Counter:
        with self._lock:
            c = self._counters.get(name)
            if c is None:
                c = self._counters[name] = Counter(name)
            return c

    def gauge(self, name: str) -> Gauge:
        with self._lock:
            g = self._gauges.get(name)
            if g is None:
                g = self._gauges[name] = Gauge(name)
            return g

    def histogram(self, name: str) -> Histogram:
        with self._lock:
            h = self._histograms.get(name)
            if h is None:
                h = self._histograms[name] = Histogram(name)
            return h

    # -- events ------------------------------------------------------------

    def event(self, name: str, **fields: Any) -> Dict[str, Any]:
        rec = {"schema": SCHEMA_VERSION,
               "ts_ms": time.monotonic() * 1e3,
               "event": name}
        rec.update(fields)
        with self._lock:
            ring = self._events.get(name)
            if ring is None:
                ring = self._events[name] = collections.deque(
                    maxlen=self._capacity.get(name, _EVENT_BUFFER_MAX))
            self._arrivals += 1
            ring.append((self._arrivals, rec))
            sinks = list(self._sinks.values())
        for fh in sinks:
            try:
                fh.write(json.dumps(rec, default=str) + "\n")
                fh.flush()
            except (OSError, ValueError):
                pass   # a dead sink must never take down the train loop
        return rec

    def events(self, name: Optional[str] = None) -> List[Dict[str, Any]]:
        """The records in memory, oldest first: of one event name, or
        of every name merged in arrival order."""
        with self._lock:
            if name is not None:
                return [rec for _, rec in self._events.get(name, ())]
            rings = [list(ring) for ring in self._events.values()]
        return [rec for _, rec in heapq.merge(*rings,
                                             key=operator.itemgetter(0))]

    def set_event_capacity(self, name: str, capacity: int) -> None:
        """Keep the newest ``capacity`` records of event ``name`` (the
        default is ``_EVENT_BUFFER_MAX`` for each name)."""
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        with self._lock:
            self._capacity[name] = int(capacity)
            ring = self._events.get(name)
            if ring is not None and ring.maxlen != capacity:
                self._events[name] = collections.deque(ring,
                                                       maxlen=capacity)

    def add_jsonl_sink(self, path: str) -> None:
        with self._lock:
            if path not in self._sinks:
                self._sinks[path] = open(path, "a")

    def remove_jsonl_sink(self, path: str) -> None:
        with self._lock:
            fh = self._sinks.pop(path, None)
        if fh is not None:
            try:
                fh.close()
            except OSError:
                pass

    # -- introspection / reset ---------------------------------------------

    def snapshot(self) -> Dict[str, Any]:
        """JSON-able view of every metric (events excluded — use
        ``events()``)."""
        with self._lock:
            return {
                "schema": SCHEMA_VERSION,
                "counters": {n: c.value for n, c in self._counters.items()},
                "gauges": {n: g.value for n, g in self._gauges.items()},
                "histograms": {n: h.summary()
                               for n, h in self._histograms.items()},
            }

    def remove(self, prefix: str) -> None:
        """Drop every metric whose name starts with ``prefix``.

        Lets a subsystem reset its slice (``step_cache.reset_stats()``)
        without clobbering unrelated metrics.
        """
        with self._lock:
            for table in (self._counters, self._gauges, self._histograms):
                for name in [n for n in table if n.startswith(prefix)]:
                    del table[name]

    def clear_events(self) -> None:
        with self._lock:
            self._events.clear()

    def reset(self) -> None:
        with self._lock:
            self._counters.clear()
            self._gauges.clear()
            self._histograms.clear()
            self._events.clear()


# -- process-global default registry ---------------------------------------

_default = MetricsRegistry()


def get_registry() -> MetricsRegistry:
    return _default


def counter(name: str) -> Counter:
    return _default.counter(name)


def gauge(name: str) -> Gauge:
    return _default.gauge(name)


def histogram(name: str) -> Histogram:
    return _default.histogram(name)


def event(name: str, **fields: Any) -> Dict[str, Any]:
    return _default.event(name, **fields)


def events(name: Optional[str] = None) -> List[Dict[str, Any]]:
    return _default.events(name)
