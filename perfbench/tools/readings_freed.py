"""Readings for the limits of ``correct``, for a serving cell whose engine
and reference do not fit the chip side by side (``readings.py`` keeps
one engine warm across seeds and runs the reference beside it).

    python3 perfbench/tools/readings_freed.py --workload <cell> \\
        --seeds N [--first-seed N] [--seconds S] [--controls int8,fp8] \\
        [--fault altered_token|bf16_router]

Each seed is one run of the cell as ``run.py`` makes it (its own engine,
freed before the reference), then the controls on that run's own
requests: the lower-precision reference's first choices at the same
positions.  ``--fault`` plants a fault in the program instead:
``altered_token`` changes one served token of every session;
``bf16_router`` computes every router's scores from operands rounded to
bfloat16 (where the program computes them in float32); ``xla_tiers`` is
no fault (every kernel's XLA tier).  ``--taus`` judges each run's
requests again under other near-tie thresholds of the reference.  One
JSON line a seed; not part of a benchmark run.
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


def altered_token(cell):
    vocab = cell.family.vocab(cell.config)

    def fault(loop):
        def alter(tr, s):
            if len(s.out) == 2 and not getattr(s, "_altered", False):
                s._altered = True
                s.out[-1] = s.pending_tok = (s.out[-1] + 1) % vocab
        loop.on_token = alter
    return fault


def bf16_router(cell):
    """The program's router with its operands rounded to bfloat16."""
    import jax.numpy as jnp
    from apex_tpu.parallel import routed_experts as rx
    plain = rx.group_limited_route

    def rounded(x, w_router, bias, **kw):
        return plain(x.astype(jnp.bfloat16), w_router.astype(jnp.bfloat16),
                     bias, **kw)

    def fault(loop):
        rx.group_limited_route = rounded
    return fault


def xla_tiers(cell):
    """No fault: every kernel's XLA tier in the Pallas tier's place (a
    reading that does not move clears the kernels)."""
    from apex_tpu.kernels import dispatch

    def fault(loop):
        dispatch._forced[0] = "off"
    return fault


FAULTS = {"altered_token": altered_token, "bf16_router": bf16_router,
          "xla_tiers": xla_tiers}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=3_000_000_001)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--controls", default="")
    ap.add_argument("--fault", choices=sorted(FAULTS))
    ap.add_argument("--taus", default="",
                    help="judge each run's requests again under these "
                         "near-tie thresholds of the reference")
    a = ap.parse_args(argv)
    import jax
    cell = cells.Cell(a.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        raise SystemExit("readings: needs the cell's TPU chips")
    env = Env(T_START, devices, sut.enable_compile_cache(),
              os.path.join(BENCH_DIR, ".trace", "readings"))
    kind = cells.kind_module(cell.kind, cell.repo)
    limits = cell.settings["limits"]
    fault = FAULTS[a.fault](cell) if a.fault else None
    for i in range(a.seeds):
        seed = a.first_seed + 7919 * i
        args = types.SimpleNamespace(seed=seed, seconds=a.seconds, trace=0)
        result = kind.run(cell, args, env, fault=fault)
        row = {"seed": seed, "fault": a.fault,
               "tokens_per_s": result["end_to_end"]["serve_tokens_per_s"],
               "tokens": int(len(result["gaps"])),
               "not_judged": int((result["margins"] == float("inf")).sum()),
               "flips": int((result["gaps"] > 0).sum()),
               "program": result["compared"], "correct": result["correct"]}
        for q in [c for c in a.controls.split(",") if c]:
            gaps, margins = serve_common.served_gaps(
                cell, seed, result["samples"], control=q)
            numbers = serve_common.gap_numbers(gaps, margins)
            ok, _ = correct.judge(numbers, limits)
            row["control_" + q] = dict(numbers, correct=ok,
                                       flips=int((gaps > 0).sum()))
        ref = cell.reference
        for tau in [float(t) for t in a.taus.split(",") if t]:
            was, ref.NEAR_TIE = ref.NEAR_TIE, tau
            ref._gap_fn.cache_clear()
            try:
                gaps, margins = serve_common.served_gaps(
                    cell, seed, result["samples"])
            finally:
                ref.NEAR_TIE = was
                ref._gap_fn.cache_clear()
            row[f"tau_{tau:g}"] = dict(
                serve_common.gap_numbers(gaps, margins),
                not_judged=int((margins == float("inf")).sum()),
                flips=int((gaps > 0).sum()), widest=float(gaps.max()))
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
