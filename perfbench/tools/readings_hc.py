"""Readings for the limits of ``correct`` in a cell whose model's residual
is several streams (``reference_latent_moe_hc.py``): ``readings_freed.py``'s
runs (each seed its own engine, freed before the reference; the controls
on the run's own requests; ``--taus``), with the faults of such a model
planted in the program.

    python3 perfbench/tools/readings_hc.py --workload <cell> \\
        --seeds N [--first-seed N] [--seconds S] [--controls int8,fp8] \\
        [--taus 0,1e-3,...] [--fault h_res_identity|bf16_streams|
        sinkhorn_5|altered_token|xla_tiers]

``h_res_identity``: every mapping's ``H_res`` is the identity (a plain
residual of four streams; ``H_pre`` and ``H_post`` as computed).
``bf16_streams``: the streams are rounded to bfloat16 where each mix
leaves them (the buffers stay float32).  ``sinkhorn_5``: 5 Sinkhorn
iterations in place of the configuration's.  The kernels' module and
the model's own mappings are patched, so the engine, its scheduler and
its pool stay what they are.  The other faults are
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


def h_res_identity(cell):
    import jax.numpy as jnp
    from apex_tpu.kernels import hyper_connection as hc
    plain = hc.hyper_conn_pre

    def pre(x, phi, alpha, bias, *, n, **kw):
        h, mix = plain(x, phi, alpha, bias, n=n, **kw)
        for i in range(n):
            for j in range(n):
                mix = mix.at[:, hc.mix_lane("res", i, j)].set(
                    jnp.float32(i == j))
        return h, mix

    def fault(loop):
        hc.hyper_conn_pre = pre
    return fault


def bf16_streams(cell):
    import jax.numpy as jnp
    from apex_tpu.kernels import hyper_connection as hc
    plain = hc.hyper_conn_post

    def post(x, y, mix, *, n):
        return plain(x, y, mix, n=n).astype(jnp.bfloat16).astype(
            jnp.float32)

    def fault(loop):
        hc.hyper_conn_post = post
    return fault


def sinkhorn_5(cell):
    def fault(loop):
        # every sublayer's mapping (the modules that iterate)
        for _, m in loop.eng.model.named_modules():
            if hasattr(m, "iters"):
                m.iters = 5
    return fault


readings_freed.FAULTS.update(h_res_identity=h_res_identity,
                             bf16_streams=bf16_streams, sinkhorn_5=sinkhorn_5)


if __name__ == "__main__":
    readings_freed.main()
