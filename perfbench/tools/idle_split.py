"""The device's idle time in a serving cell, split by what the host was
doing (``readers/idle.py``), from one traced run with what the harness's
reduced trace does not keep: the trace's zero on the profiler's host
clock (its ``profile_start_time``) and how much earlier its device plane
stamps an event than its host plane does.  That offset is bounded by the
runtime's own host events: a program starts after it was enqueued
(``DoEnqueueProgram`` ends) and its end is signalled after it ended
(``tpu::System::Execute=>Done`` starts); the split takes the middle of
the bounds.

    python3 perfbench/tools/idle_split.py --workload <cell> --seed N \\
        [--seconds S]

Runs the cell as ``perfbench/run.py --trace 1`` does, in this process and
on one chip, then prints the offset's bounds, the reader's line and
``decode_idle_in_{dispatch,fetch,host}_ms``.  Not part of a benchmark
run: the harness's reduction keeps neither number yet (PERF.md section
7).
"""
from __future__ import annotations

import argparse
import os
import sys

TOOLS = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(TOOLS))

import run  # noqa: E402  (perfbench/run.py: its clock starts here)
from pb import cells  # noqa: E402
from pb import trace as _trace  # noqa: E402

ENQUEUED = "DoEnqueueProgram"
DONE = "tpu::System::Execute=>Done"


def device_offset(modules, enqueued, done):
    """``(lo, hi)``: bounds in ns on how much earlier the device plane
    stamps a program than the host plane, from the programs in start
    order against their enqueues and their completions in the same
    order; None where the counts differ or the bounds cross."""
    mods = sorted((s, d) for _, s, d in modules)
    if not mods or len(mods) != len(enqueued) or len(mods) != len(done):
        return None
    lo = max(e + ed - s for (s, _), (e, ed) in zip(mods, sorted(enqueued)))
    hi = min(c - (s + d) for (s, d), (c, _) in zip(mods, sorted(done)))
    return (lo, hi) if lo <= hi else None


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=45.0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    kept, contexts = {}, []
    load, reduce, metric_reader = \
        _trace.load_xplane, _trace.reduce, cells.metric_reader

    def load_xplane(path):
        from jax.profiler import ProfileData
        for plane in ProfileData.from_file(path).planes:
            if plane.name == "Task Environment":
                kept["start_ns"] = dict(plane.stats).get("profile_start_time")
            elif plane.name == "/host:CPU":
                for line in plane.lines:
                    for e in line.events:
                        if e.name in (ENQUEUED, DONE):
                            kept.setdefault(e.name, []).append(
                                (e.start_ns, e.duration_ns))
        return load(path)

    def reduce_kept(trace, window_s):
        out = reduce(trace, window_s)
        bounds = device_offset(out.get("modules", []),
                               kept.get(ENQUEUED, []), kept.get(DONE, []))
        out["start_ns"] = kept.get("start_ns")
        out["device_offset_bounds_ns"] = bounds
        out["device_offset_ns"] = None if bounds is None else sum(bounds) / 2
        return out

    def reader_seeing_ctx(name, repo=cells.REPO):
        fn, kw = metric_reader(name, repo)

        def read(ctx, **k):
            if not contexts:
                contexts.append(ctx)
            return fn(ctx, **k)
        return read, kw

    _trace.load_xplane, _trace.reduce, cells.metric_reader = \
        load_xplane, reduce_kept, reader_seeing_ctx
    rc = run.main(["--workload", args.workload, "--seed", str(args.seed),
                   "--seconds", str(args.seconds), "--trace", "1"])
    if not contexts or not contexts[0].get("trace"):
        print("[idle_split] no trace was read", flush=True)
        return rc or 1
    ctx = contexts[0]
    tr = ctx["trace"]
    bounds = tr["device_offset_bounds_ns"]
    early = "? .. ?" if bounds is None else \
        f"{bounds[0] / 1e3:.1f} .. {bounds[1] / 1e3:.1f}"
    print(f"[idle_split] trace zero {tr['start_ns']}; the device plane "
          f"stamps {early} us early against the host plane "
          f"({len(tr.get('modules', []))} programs, "
          f"{len(kept.get(ENQUEUED, []))} enqueued)", flush=True)
    idle = cells._module_at(cells.REPO, "readers", "idle")
    for part in ("dispatch", "fetch", "host"):
        print(f"[idle_split] decode_idle_in_{part}_ms "
              f"{idle.decode_idle_ms(ctx, part)}", flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
