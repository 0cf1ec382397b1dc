"""Readings for the limits of ``correct`` in a cell whose model mixes
window and full attention layers behind a router that can tip
(``reference_gqa_moe.py``): ``readings_freed.py``'s runs, with the
faults of such a model planted in the program and every threshold of the
near-tie rule judged from ONE pass of the reference.

    python3 perfbench/tools/readings_window.py --workload <cell> \\
        --seeds N [--first-seed N] [--seconds S] [--controls int8,fp8] \\
        [--taus 0,1e-4,...] [--fault band_minus_1|band_plus_1|
        window_on_full|altered_token|xla_tiers]

``band_minus_1`` / ``band_plus_1``: every window layer reads one key
fewer / one key more than its window (the blocks are still retired by
the window itself, so the key too many may lie in a block that went back
to the pool).  ``window_on_full``: every full-attention layer is handed
the window layers' window.  The layers' two readers are wrapped, so the
cache's groups, pools and tables stay what the engine made them.  The
other faults are ``readings_freed.py``'s.  One JSON line a seed: the
run's own number, and under ``taus`` the number, the positions not
judged and the flips at each threshold, for the program's tokens and for
each control's; not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import types

T_START = time.perf_counter()
TOOLS = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, TOOLS)
sys.path.insert(0, os.path.dirname(TOOLS))

import readings_freed
from pb import cells, correct, serve_common, sut, weights
from pb.runenv import Env


def _hand(blk, window):
    """Both readers of ``blk`` handed ``window`` whatever its own is."""
    for name in ("read_decode", "read_chunk"):
        read = getattr(blk, name)
        setattr(blk, name, lambda q, pool, layer, tables, pos, _own,
                read=read: read(q, pool, layer, tables, pos, window))


def _band(delta):
    def make(cell):
        def fault(loop):
            for blk in loop.eng.model.blocks:
                if blk.window is not None:
                    _hand(blk, blk.window + delta)
        return fault
    return make


def window_on_full(cell):
    def fault(loop):
        blocks = loop.eng.model.blocks
        window = min(b.window for b in blocks if b.window is not None)
        for blk in blocks:
            if blk.window is None:
                _hand(blk, window)
    return fault


FAULTS = dict(readings_freed.FAULTS, band_minus_1=_band(-1),
              band_plus_1=_band(1), window_on_full=window_on_full)


def gaps_margins_ties(cell, seed, samples, control=None):
    """``serve_common.served_gaps`` with the reference's ``ties`` beside
    the gaps and margins, nothing left out by a threshold."""
    import jax.numpy as jnp
    import numpy as np
    cfg, family = cell.config, cell.family
    w = weights.make_weights(family, cfg, seed, cfg["serve"]["weights_dtype"])
    s_max = family.max_positions(cfg)
    rows = serve_common.REF_ROWS
    out = ([], [], [])
    for r in range(0, len(samples), rows):
        padded = np.zeros((rows, s_max), np.int32)
        picked = np.zeros((rows, s_max), np.int32)
        spans = []
        for i, (prompt, served) in enumerate(samples[r:r + rows]):
            ids = (list(prompt) + list(served))[:s_max]
            n_p, n_out = len(prompt), len(ids) - len(prompt)
            padded[i, :len(ids)] = ids
            picked[i, n_p - 1:n_p - 1 + n_out] = ids[n_p:]
            spans.append((n_p - 1, n_p - 1 + n_out))
        got = cell.reference.gaps_margins_ties(
            cfg, w, jnp.asarray(padded), jnp.asarray(picked), control)
        for acc, x in zip(out, got):
            x = np.asarray(x)
            acc += [x[i, a:b] for i, (a, b) in enumerate(spans)]
    return tuple(np.concatenate(acc) for acc in out)


def at_thresholds(gaps, margins, ties, taus, limits) -> dict:
    import numpy as np
    out = {}
    for tau in taus:
        near = ties < tau
        g = np.where(near, 0.0, gaps)
        m = np.where(near, np.inf, margins)
        numbers = serve_common.gap_numbers(g, m)
        out[f"{tau:g}"] = dict(
            numbers, correct=correct.judge(numbers, limits)[0],
            not_judged=int(near.sum()), flips=int((g > 0).sum()),
            widest=float(g.max()))
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=3_300_000_001)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--controls", default="")
    ap.add_argument("--fault", choices=sorted(FAULTS))
    ap.add_argument("--taus", default="0,2e-5,5e-5,1e-4,2e-4,4e-4")
    a = ap.parse_args(argv)
    import jax
    cell = cells.Cell(a.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        raise SystemExit("readings: needs the cell's TPU chips")
    env = Env(T_START, devices, sut.enable_compile_cache(),
              os.path.join(os.path.dirname(TOOLS), ".trace", "readings"))
    kind = cells.kind_module(cell.kind, cell.repo)
    limits = cell.settings["limits"]
    taus = [float(t) for t in a.taus.split(",") if t]
    fault = FAULTS[a.fault](cell) if a.fault else None
    for i in range(a.seeds):
        seed = a.first_seed + 7919 * i
        args = types.SimpleNamespace(seed=seed, seconds=a.seconds, trace=0)
        result = kind.run(cell, args, env, fault=fault)
        row = {"seed": seed, "fault": a.fault,
               "tokens_per_s": result["end_to_end"]["serve_tokens_per_s"],
               "tokens": int(len(result["gaps"])),
               "program": result["compared"], "correct": result["correct"]}
        t = env.now()
        row["taus"] = at_thresholds(
            *gaps_margins_ties(cell, seed, result["samples"]), taus, limits)
        row["reference_pass_s"] = env.now() - t
        for q in [c for c in a.controls.split(",") if c]:
            row["control_" + q] = at_thresholds(
                *gaps_margins_ties(cell, seed, result["samples"], q),
                taus, limits)
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
