"""Worker for the REAL 2-process distributed test (launched by
``apex_tpu.parallel.multiproc``): each process owns one CPU device,
``init_distributed()`` wires them through ``jax.distributed``, and a DP
fused train step runs over the global 2-device mesh with each process
feeding its own half of the batch.

Writes ``rank<i>.npz`` (losses + the first fp32 master parameter after
training) into ``--outdir``; the parent test asserts cross-process
equality and parity with a single-process oracle — the
``tests/distributed/test_amp_master_params.py`` oracle, actually
multi-process (reference analogue:
/root/reference/tests/distributed/amp_master_params/run.sh:2, which runs
``torch.distributed.launch`` with 2 GPUs).
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--outdir", required=True)
    ap.add_argument("--steps", type=int, default=4)
    ap.add_argument("--local_rank", type=int, default=0)
    args = ap.parse_args()

    import jax
    # one local CPU device per process, whatever the caller's environment
    # says about platforms and virtual devices
    jax.config.update("jax_platforms", "cpu")
    # cross-process collectives on the CPU backend ride gloo
    jax.config.update("jax_cpu_collectives_implementation", "gloo")

    from apex_tpu.parallel import init_distributed
    init_distributed()   # consumes APEX_TPU_* exported by the launcher

    assert jax.process_count() == 2, jax.process_count()
    assert len(jax.devices()) == 2, jax.devices()
    rank = jax.process_index()

    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import apex_tpu.nn as nn
    from apex_tpu.nn import functional as F
    from apex_tpu.optimizers import FusedSGD
    from apex_tpu.training import make_train_step

    nn.manual_seed(7)
    model = nn.Sequential(nn.Linear(16, 32), nn.GELU(), nn.Linear(32, 8))
    opt = FusedSGD(list(model.parameters()), lr=0.05, momentum=0.9)
    step = make_train_step(model, opt,
                           lambda o, t: F.cross_entropy(o, t),
                           half_dtype=jnp.bfloat16, loss_scale=1.0,
                           axis_name="data")

    mesh = Mesh(np.array(jax.devices()), ("data",))
    sharded = jax.jit(jax.shard_map(
        step._step_fn, mesh=mesh,
        in_specs=(P(), P("data"), P("data")),
        out_specs=(P(), P()), check_vma=False))

    # deterministic global batch; THIS process materializes only its own
    # half and contributes it as its device's shard of the global array
    rng = np.random.default_rng(0)
    xg = rng.standard_normal((8, 16)).astype(np.float32)
    yg = rng.integers(0, 8, (8,))
    bsh = NamedSharding(mesh, P("data"))

    def globalize(arr):
        local = arr[rank * 4:(rank + 1) * 4]
        return jax.make_array_from_process_local_data(
            bsh, local, arr.shape)

    x, y = globalize(xg), globalize(yg.astype(np.int32))

    # the state is replicated: every leaf must become a global array
    # before the multi-process jit consumes it
    rep = NamedSharding(mesh, P())
    state = jax.tree.map(
        lambda a: jax.make_array_from_callback(
            a.shape, rep, lambda idx: np.asarray(a)[idx]), step.state)

    losses = []
    for _ in range(args.steps):
        state, loss = sharded(state, x, y)
        losses.append(float(loss))   # fully-replicated: fetchable anywhere

    # the first master param is replicated; this process's addressable
    # shard is the full array
    m0 = np.asarray(state.master_params[0].addressable_data(0))
    np.savez(os.path.join(args.outdir, f"rank{rank}.npz"),
             losses=np.asarray(losses), m0=m0)
    print(f"rank {rank}: ok, losses={losses}")


if __name__ == "__main__":
    main()
