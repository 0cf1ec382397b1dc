"""What every traffic kind needs from a run: the clock that started
with the process, lines of information on standard output, the profiler
window, and the device's own readings."""
from __future__ import annotations

import os
import shutil
import sys
import time

from . import trace as _trace


class Env:
    def __init__(self, t_start: float, devices, cache_counts,
                 trace_dir: str):
        self.t_start = t_start          # perf_counter at process start
        self.devices = devices
        self.cache = cache_counts
        self.trace_dir = trace_dir

    @staticmethod
    def now() -> float:
        return time.perf_counter()

    def since_start(self) -> float:
        return time.perf_counter() - self.t_start

    def say(self, msg: str) -> None:
        print(f"[perfbench +{self.since_start():7.2f}s] {msg}", flush=True)

    # -- profiler --------------------------------------------------------

    def start_trace(self) -> None:
        import jax
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        os.makedirs(self.trace_dir, exist_ok=True)
        # no Python frames: they are most of a trace's bytes and of what
        # tracing costs the host (the serve loop's tick rate halved with
        # them on; my chip run, PR 25); TraceAnnotations stay
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)

    def stop_trace(self, window_s: float) -> dict:
        import jax
        jax.profiler.stop_trace()
        t0 = time.perf_counter()
        path = _trace.newest_xplane(self.trace_dir)
        size = os.path.getsize(path)
        reduced = _trace.reduce(_trace.load_xplane(path), window_s)
        self.say(f"trace: {size / 1e6:.1f} MB read and reduced in "
                 f"{time.perf_counter() - t0:.1f} s; busy "
                 f"{reduced['busy_s']:.4f} s of {window_s:.4f} s on "
                 f"{reduced['n_devices']} device plane(s)")
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        return reduced

    # -- device readings ---------------------------------------------------

    def memory_peak(self, n_chips: int) -> dict:
        """The fullest chip's peak, read at the window's close while the
        program's state is still live.  On this backend
        ``peak_bytes_in_use`` counts live arrays only and the compiled
        programs' temporaries are held under ``peak_bytes_reserved``
        (PERF.md section 3).  The two peaks need not coincide (the
        live arrays' peak may date from set-up), so the peak is what is
        live now, while the window's programs run, plus the programs'
        reserve, or the live arrays' own peak where that is larger."""
        best = {"total": 0, "in_use": 0, "peak_in_use": 0, "reserved": 0}
        for d in self.devices[:n_chips]:
            st = d.memory_stats() or {}
            peak_in_use = int(st.get("peak_bytes_in_use", 0))
            in_use = int(st.get("bytes_in_use", peak_in_use))
            reserved = int(st.get("peak_bytes_reserved", 0))
            total = max(peak_in_use, in_use + reserved)
            if total > best["total"]:
                best = {"total": total, "in_use": in_use,
                        "peak_in_use": peak_in_use, "reserved": reserved}
        return best

    def device_dict(self, n_chips: int, peak_bytes: int) -> dict:
        d0 = self.devices[0]
        return {"platform": d0.platform, "kind": d0.device_kind,
                "count": n_chips, "memory_peak_bytes": int(peak_bytes)}


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0..100) by linear interpolation."""
    xs = sorted(values)
    if not xs:
        return float("nan")
    k = (len(xs) - 1) * q / 100.0
    lo = int(k)
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (k - lo))


def eprint(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)
