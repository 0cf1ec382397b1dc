"""perfbench: one run of one cell of BENCHMARK.json.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result as one JSON object;
every line before it is information.  Without a TPU, or with fewer
chips than the cell asks for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

from pb import cells, peaks
from pb.runenv import Env, eprint


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def require_chips(n: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"perfbench: needs a TPU; JAX found {len(devices)} x "
            f"{devices[0].platform} ({devices[0].device_kind})")
    if len(devices) < n:
        raise SystemExit(f"perfbench: this cell needs {n} chips; JAX "
                         f"found {len(devices)}")
    return devices


def metrics_of(cell, args, result, env) -> dict:
    """With ``--trace 0`` the cell's end-to-end metrics; with
    ``--trace 1`` its per-layer metrics, each from its own reader.  A
    reader that finds nothing to read leaves its metric out."""
    if not args.trace:
        return {m["name"]: {"value": result["end_to_end"][m["name"]],
                            "unit": m["unit"]}
                for m in cell.end_to_end}
    ctx = {"cfg": cell.config, "family": cell.family, "mix": cell.traffic,
           # main() has refused an unlisted TPU already; off the chip
           # (tests) there are no peaks and the readers of shares of a
           # peak find nothing to read
           "peaks": peaks.PEAKS.get(env.devices[0].device_kind),
           "counters": result["counters"], "trace": result.get("trace"),
           "peak": result["peak"], "window_s": result["window_s"],
           "end_to_end": result["end_to_end"]}
    out = {}
    for m in cell.per_layer:
        reader, kw = cells.metric_reader(m["name"], cell.repo)
        value = reader(ctx, **kw)
        if value is None:
            env.say(f"per-layer metric {m['name']}: nothing to read")
            continue
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def result_line(cell, args, result, env) -> dict:
    device = env.device_dict(cell.chips, result["peak"]["total"])
    line = {"correct": bool(result["correct"]),
            "attempted": int(result["attempted"]),
            "failed": int(result["failed"]),
            "metrics": metrics_of(cell, args, result, env),
            "device": device}
    tr = result.get("trace")
    if args.trace and tr and tr.get("n_devices"):
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        line["breakdown"] = {"device_ops": tr["device_ops"],
                             "idle_gaps": tr["idle_gaps"]}
    line["compared"] = result["compared"]
    return line


def run_cell(cell, args, env) -> dict:
    kind = cells.kind_module(cell.kind, cell.repo)
    result = kind.run(cell, args, env)
    env.say(f"compile cache {env.cache.directory}: {env.cache.hits} hits, "
            f"{env.cache.misses} misses")
    return result_line(cell, args, result, env)


def main(argv=None) -> int:
    args = parse(argv)
    cell = cells.Cell(args.workload)
    devices = require_chips(cell.chips)
    peaks.peaks_for(devices[0].device_kind)     # an unlisted chip is an error
    from pb import sut
    cache = sut.enable_compile_cache()
    env = Env(T_START, devices, cache,
              os.path.join(BENCH_DIR, ".trace", cell.name))
    env.say(f"cell {cell.name}: config {cell.config_name}, traffic "
            f"{cell.traffic_name} ({cell.kind}), {cell.chips} chip(s), "
            f"seed {args.seed}, {args.seconds} s, trace {args.trace}")
    line = run_cell(cell, args, env)
    for name, (value, limit) in line["compared"].items():
        eprint(f"compared {name}: {value!r} limit {limit!r}")
    eprint(f"correct: {line['correct']}")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
