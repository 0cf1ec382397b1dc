"""Readings for the limits of ``correct``: many seeds in one process.

    python3 perfbench/tools/readings.py --workload <cell> --seeds 12 \\
        [--first-seed N] [--seconds S] [--faults 3] [--own-int8 3]

For each seed: the program's numbers against the float32 reference (the
lower reading is their largest), the control's (the reference one
precision down in the program's place; the upper reading is its
smallest), each put through ``correct.judge`` with the cell's limits,
and, for a training cell on the first ``--faults`` seeds, the planted
faults'.  For a serving cell ``--own-int8 N`` then serves the first N
seeds again from the program's own int8 cache (``cache_dtype`` int8,
every other setting the cell's) as one more control.  Every token's gap and
margin go to ``chiprun_out/``, so that another number can be tried on
the same readings.  Not part of a benchmark run; PERF.md
gives what it printed when the limits were set.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import types

T_START = time.perf_counter()
BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH_DIR)

from pb import cells, correct, serve_common, sut
from pb.runenv import Env


def _rows(share):
    def wrapper(step):
        def call(x, y):
            n = max(1, int(x.shape[0] * share))
            return step(x[:n], y[:n])
        return call
    return wrapper


def train_readings(cell, seeds, n_faults, env):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from pb import traffic
    train = cells.kind_module("train", cell.repo)
    cfg, mix, family = cell.config, cell.traffic, cell.family
    devices = list(env.devices[:cell.chips])
    rows = []
    for k, seed in enumerate(seeds):
        ref = train.reference_readings(cell, seed)
        row = {"seed": seed}

        def program(wrapper=None):
            step, mesh = sut.build_train_step(family, cfg, seed,
                                              mix["parallel"], devices)
            where = NamedSharding(mesh, P("data")) if mesh is not None \
                else devices[0]
            feed = sut.train_feed(traffic.train_batches(
                mix, seed, family.vocab(cfg)), where)
            try:
                return train.drive_first_steps(
                    cell, seed, step, iter(feed),
                    wrapper(step) if wrapper else None)
            finally:
                feed.close()
        row["program"] = correct.train_numbers(program(), ref)
        for q in ("int8", "fp8"):
            row["control_" + q] = correct.train_numbers(
                train.reference_readings(cell, seed, quant=q), ref)
        if k < n_faults:
            row["fault_half_batch"] = correct.train_numbers(
                program(_rows(0.5)), ref)
            row["fault_quarter_batch"] = correct.train_numbers(
                program(_rows(0.25)), ref)
        rows.append(row)
        env.say(json.dumps({a: ({n: v for n, v in b.items()
                                 if not n.startswith("_")}
                                if isinstance(b, dict) else b)
                            for a, b in row.items()}))
        _save(cell, rows)
    return rows


def _save(cell, rows, tag=""):
    os.makedirs("chiprun_out", exist_ok=True)
    with open(f"chiprun_out/readings_{cell.name}{tag}.json", "w") as f:
        json.dump(rows, f, indent=1)


def _gap_row(gaps, margins, limits) -> dict:
    """One served run's numbers and its verdict under the cell's
    limits."""
    numbers = serve_common.gap_numbers(gaps, margins)
    ok, _ = correct.judge(numbers, limits)
    return dict(numbers, correct=ok, tokens=int(len(gaps)),
                flips=int((gaps > 0).sum()))


def _serve_seeds(cell, seeds, seconds, env, controls, tag=""):
    """Serve ``seeds`` from one engine, the weights swapped between
    them, at the cell's own load.  Every token's gap and margin go to
    ``chiprun_out/gaps_<cell><tag>_<seed>.npz``."""
    import numpy as np
    kind = cells.kind_module(cell.kind, cell.repo)
    cfg, family = cell.config, cell.family
    limits = cell.settings["limits"]
    eng = None
    rows = []
    for seed in seeds:
        args = types.SimpleNamespace(seed=seed, seconds=seconds, trace=0)
        if eng is None:
            eng = sut.build_engine(family, cfg, seed)
            from pb import serve_loop, traffic
            serve_loop.warm_waves(
                serve_loop.Loop(eng, env), cell.traffic, family.vocab(cfg),
                cfg["serve"]["max_batch"], traffic.rng_for(seed, "warm"))
        else:
            sut.publish_weights(eng, family, cfg, seed)
        result = kind.run(cell, args, env, eng=eng)
        arrays = {"margins": result["margins"], "program": result["gaps"]}
        row = {"seed": seed, "failed": result["failed"],
               "tokens_per_s": result["end_to_end"]["serve_tokens_per_s"],
               "program": _gap_row(result["gaps"], result["margins"],
                                   limits)}
        for q in controls:
            gaps, margins = serve_common.served_gaps(
                cell, seed, result["samples"], control=q)
            arrays[q] = gaps
            row["control_" + q] = _gap_row(gaps, margins, limits)
        rows.append(row)
        env.say(json.dumps(row))
        _save(cell, rows, tag)
        np.savez_compressed(
            f"chiprun_out/gaps_{cell.name}{tag}_{seed}.npz", **arrays)
    eng.close()
    return rows


def serve_readings(cell, seeds, seconds, n_own_int8, env):
    import copy
    import gc
    import jax
    rows = _serve_seeds(cell, seeds, seconds, env, ("int8", "fp8"))
    if n_own_int8:
        # the program's own lower-precision path in the program's place
        gc.collect()
        jax.clear_caches()
        own = copy.copy(cell)
        own.config = copy.deepcopy(cell.config)
        own.config["serve"]["cache_dtype"] = "int8"
        for r, o in zip(rows, _serve_seeds(own, seeds[:n_own_int8], seconds,
                                           env, (), "_own_int8")):
            r["own_int8_cache"] = o["program"]
            r["own_int8_cache"]["tokens_per_s"] = o["tokens_per_s"]
        _save(cell, rows)
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=3_000_000_001)
    ap.add_argument("--seconds", type=float, default=12.0)
    ap.add_argument("--faults", type=int, default=3)
    ap.add_argument("--own-int8", type=int, default=0)
    a = ap.parse_args(argv)
    import jax
    cell = cells.Cell(a.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        raise SystemExit("readings: needs the cell's TPU chips")
    env = Env(T_START, devices, sut.enable_compile_cache(),
              os.path.join(BENCH_DIR, ".trace", "readings"))
    seeds = [a.first_seed + 7919 * i for i in range(a.seeds)]
    if cell.kind == "train":
        rows = train_readings(cell, seeds, a.faults, env)
        names = [n for n in rows[0]["program"] if not n.startswith("_")]
        for n in names:
            lo = max(r["program"][n] for r in rows)
            line = f"{n}: program max {lo:.3e}"
            for q in ("int8", "fp8"):
                hi = min(r["control_" + q][n] for r in rows)
                line += f", {q} control min {hi:.3e}"
            for f in ("fault_half_batch", "fault_quarter_batch"):
                got = [r[f][n] for r in rows if f in r]
                if got:
                    line += f", {f} min {min(got):.3e}"
            env.say(line)
    else:
        rows = serve_readings(cell, seeds, a.seconds, a.own_int8, env)
        for name in ("served_sq_gap_per_close_call",):
            line = f"{name}: program max " \
                f"{max(r['program'][name] for r in rows):.4e}"
            for c in ("control_int8", "control_fp8", "own_int8_cache"):
                got = [r[c][name] for r in rows if c in r]
                if got:
                    line += f", {c} min {min(got):.4e}"
            env.say(line)
        for c in ("program", "control_int8", "control_fp8",
                  "own_int8_cache"):
            got = [r[c]["correct"] for r in rows if c in r]
            env.say(f"{c}: correct on {sum(got)} of {len(got)} seeds")


if __name__ == "__main__":
    main()
