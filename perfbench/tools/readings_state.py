"""Readings for the limits of ``correct`` in a cell whose model keeps a
state of a session (``reference_hybrid_ssm_moe.py``):
``readings_window.py``'s runs (every threshold of the near-tie rule
judged from ONE pass of the reference, the controls on the run's own
requests), with the faults of such a model planted in the program.

    python3 perfbench/tools/readings_state.py --workload <cell> \\
        --seeds N [--first-seed N] [--seconds S] [--controls int8,fp8] \\
        [--taus 0,1e-4,...] [--fault bf16_state|stale_slot|
        conv_off_by_one|tail_updates_state|altered_token|xla_tiers]

``bf16_state``: every state-space layer's state is rounded to bfloat16
where a step or a chunk leaves it (the buffers stay float32, so the
program's kernel tier and its speed stay; the program keeps the state
float32).  ``stale_slot``: a slot is
not started from zero (the chunk at a session's start reads what the
slot's last session left).  ``conv_off_by_one``: what is kept of the
convolution's inputs for the next chunk or step is one row off.
``tail_updates_state``: a chunk's padded tail steps the state.  The
layers' own methods are wrapped, so the engine, its scheduler, its slots
and its buffers stay what they are.  The other faults are
``readings_freed.py``'s.  One JSON line a seed; not part of a benchmark
run.
"""
from __future__ import annotations

import os
import sys

TOOLS = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, TOOLS)
sys.path.insert(0, os.path.dirname(TOOLS))

import readings_freed
import readings_window


def _mixers(model):
    return [blk.mixer for blk in model.blocks if hasattr(blk, "mixer")]


def stale_slot(model):
    for blk in model.blocks:
        if hasattr(blk, "mixer"):
            chunk = blk.chunk
            blk.chunk = lambda ctx, x, state, n_real, first, chunk=chunk: \
                chunk(ctx, x, state, n_real, False)


def conv_off_by_one(model):
    import jax
    import jax.numpy as jnp
    for m in _mixers(model):
        inner = m.chunk

        def chunk(ctx, u, h0, before, n_real, inner=inner, m=m):
            out, h, _ = inner(ctx, u, h0, before, n_real)
            ext = jnp.concatenate([before, m._split(ctx, u)[1]], axis=0)
            return out, h, jax.lax.dynamic_slice_in_dim(
                ext, n_real - 1, m.conv_kernel - 1, axis=0)
        m.chunk = chunk


def tail_updates_state(model):
    import jax.numpy as jnp
    for m in _mixers(model):
        inner = m.chunk
        m.chunk = lambda ctx, u, h0, before, n_real, inner=inner: \
            inner(ctx, u, h0, before, jnp.int32(u.shape[0]))[:2] + (
                inner(ctx, u, h0, before, n_real)[2],)


def bf16_state(model):
    import jax

    def rounded(h):     # (a cast there and back is removed as an identity)
        return jax.lax.reduce_precision(h, exponent_bits=8, mantissa_bits=7)
    for m in _mixers(model):
        step, chunk = m.step, m.chunk

        def step_(ctx, u, state, conv, layer, slots, step=step):
            out, state, conv = step(ctx, u, state, conv, layer, slots)
            return out, state.at[layer].set(rounded(state[layer])), conv

        def chunk_(ctx, u, h0, before, n_real, chunk=chunk):
            out, h, after = chunk(ctx, u, h0, before, n_real)
            return out, rounded(h), after
        m.step, m.chunk = step_, chunk_


#: the faults planted in the model's own layers (the tests of
#: tests/test_serve_state_moe.py plant the same ones and read logits)
MODEL_FAULTS = {"stale_slot": stale_slot, "conv_off_by_one": conv_off_by_one,
                "tail_updates_state": tail_updates_state,
                "bf16_state": bf16_state}


def _on_the_model(plant):
    return lambda cell: lambda loop: plant(loop.eng.model)


readings_window.FAULTS = dict(
    readings_freed.FAULTS,
    **{name: _on_the_model(plant) for name, plant in MODEL_FAULTS.items()})


def _print_the_states(kind, controls):
    """``kind.run`` with one more JSON line a seed: the widest head's gap
    of every session whose state was compared (``kinds/closed_state.py``),
    and each control's in the program's place."""
    import json
    run = kind.run

    def run_and_print(cell, args, env, **kw):
        out = run(cell, args, env, **kw)
        if "state" in out:
            st = out["state"]
            row = {"seed": args.seed, "state_gap": [
                {"position": s["position"], "slot": s["slot"],
                 "head_widest": float(g.max()), "head_median":
                 float(sorted(g)[len(g) // 2])}
                for s, g in zip(st["sessions"], st["gaps"])]}
            for q in controls:
                row["state_gap_control_" + q] = [float(g.max()) for g in
                    kind.state_gaps(cell, args.seed, st, control=q)]
            print(json.dumps(row), flush=True)
        return out
    kind.run = run_and_print


if __name__ == "__main__":
    from pb import cells
    _args = sys.argv[1:]
    _controls = _args[_args.index("--controls") + 1].split(",") \
        if "--controls" in _args else []
    _print_the_states(cells.kind_module("closed_state"),
                      [q for q in _controls if q])
    readings_window.main()
