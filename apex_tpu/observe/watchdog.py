"""Stall watchdog: a heartbeat thread that notices when training stops.

A backend call that never returns hangs the dispatch loop with nothing
to say so — the failure mode this module exists for.
The train loop (and anything else that makes forward progress) calls
``heartbeat()``; ``StallWatchdog`` polls and, when no heartbeat lands
within ``deadline_s``, emits one typed ``watchdog.stall`` diagnostic
carrying the last open span, the last completed step, the backend
state, and a hint — then stays quiet until progress resumes (one
diagnostic per distinct stall, not one per poll).

``heartbeat()`` is a lock + two float stores: cheap enough to call
every step. It is host-side instrumentation (OBS-IN-JIT applies).
"""
from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, Optional

from . import registry as _registry
from . import spans as _spans

STALL_HINT = (
    "backend did not respond: a chip belongs to one process at a time — "
    "check that no other process holds it; if the backend is healthy, "
    "check the last span below for the phase that stopped making progress"
)

_hb_lock = threading.Lock()
_last_beat: Optional[float] = None
_last_step: Optional[int] = None


def heartbeat(step: Optional[int] = None) -> None:
    """Record forward progress; called by TrainStep after each window."""
    global _last_beat, _last_step
    with _hb_lock:
        _last_beat = time.monotonic()
        if step is not None:
            _last_step = step


def last_heartbeat():
    with _hb_lock:
        return _last_beat, _last_step


class StallWatchdog:
    """Daemon thread firing a diagnostic when heartbeats stop.

    >>> wd = StallWatchdog(deadline_s=30.0)
    >>> wd.start()
    ... # train; TrainStep.__call__ heartbeats automatically
    >>> wd.stop()
    """

    def __init__(self, deadline_s: float, poll_s: Optional[float] = None,
                 on_stall: Optional[Callable[[Dict[str, Any]], None]] = None):
        if deadline_s <= 0:
            raise ValueError(f"deadline_s must be > 0, got {deadline_s}")
        self.deadline_s = float(deadline_s)
        self.poll_s = float(poll_s) if poll_s is not None \
            else self.deadline_s / 4.0
        self.on_stall = on_stall
        self.stalls: list = []       # diagnostics, for tests / callers
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._start_time: Optional[float] = None
        self._fired_for: Optional[float] = None   # beat we already flagged

    def start(self) -> "StallWatchdog":
        if self._thread is not None:
            return self
        self._start_time = time.monotonic()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="apex-tpu-stall-watchdog")
        self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=self.poll_s * 4 + 1.0)
            self._thread = None

    def __enter__(self) -> "StallWatchdog":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # -- internals ---------------------------------------------------------

    def _run(self) -> None:
        while not self._stop.wait(self.poll_s):
            beat, step = last_heartbeat()
            anchor = beat if beat is not None else self._start_time
            silence = time.monotonic() - anchor
            if silence < self.deadline_s:
                continue
            if self._fired_for == anchor:
                continue             # already diagnosed this stall
            self._fired_for = anchor
            self._fire(silence, step)

    def _fire(self, silence_s: float, step: Optional[int]) -> None:
        try:
            import jax
            backend = jax.default_backend()
        except Exception as e:       # backend wedged/uninitialized
            backend = f"unavailable: {type(e).__name__}"
        diag = {
            "deadline_s": self.deadline_s,
            "since_last_step_s": silence_s,
            "last_step": step,
            "last_span": _spans.last_span(),
            "backend": backend,
            "hint": STALL_HINT,
        }
        self.stalls.append(diag)
        _registry.event("watchdog.stall", **diag)
        if self.on_stall is not None:
            try:
                self.on_stall(diag)
            except Exception:
                pass                 # a bad callback must not kill the thread
