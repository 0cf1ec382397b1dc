"""Long-context GPT pretraining with sequence parallelism — the sequence
dimension shards over a mesh axis, attention rides the ring
(parallel/ring_attention.py), and each block rematerializes in backward:
per-device activation memory is O(S / n_devices) at block boundaries, so
global context length scales linearly with the ring size.

The reference has no long-context story (SURVEY.md §5); this is the
TPU-native recipe.  Uses the first ``--devices`` devices JAX finds and
exits with a message when there are fewer (examples/README.md has the
virtual-mesh recipe for a host without that many).

Run: ``python main_sp.py --devices 8 --seq-len 1024 --steps 20``
"""
import argparse
import sys
import time


def parse_args():
    p = argparse.ArgumentParser(
        description="sequence-parallel GPT pretrain + apex_tpu")
    p.add_argument("--devices", type=int, default=8,
                   help="ring size (mesh axis length)")
    p.add_argument("--batch", type=int, default=2)
    p.add_argument("--seq-len", type=int, default=1024,
                   help="GLOBAL sequence length (shards over the ring)")
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--lr", type=float, default=6e-4)
    p.add_argument("--layers", type=int, default=4)
    p.add_argument("--hidden", type=int, default=128)
    p.add_argument("--heads", type=int, default=8)
    p.add_argument("--vocab", type=int, default=50257)
    p.add_argument("--no-remat", action="store_true")
    p.add_argument("--print-freq", type=int, default=5)
    return p.parse_args()


def main():
    args = parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    import apex_tpu.nn as nn
    from apex_tpu.models import GptModel
    from apex_tpu.nn import functional as F
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.training import make_train_step

    devices = jax.devices()[:args.devices]
    if len(devices) < args.devices:
        raise SystemExit(f"need {args.devices} devices, have {len(devices)}")
    if args.seq_len % args.devices:
        raise SystemExit("--seq-len must divide by --devices")
    mesh = Mesh(np.array(devices), ("sp",))

    nn.manual_seed(0)
    model = GptModel(vocab_size=args.vocab, hidden=args.hidden,
                     layers=args.layers, heads=args.heads,
                     max_positions=args.seq_len, attn_dropout=0.0,
                     remat=not args.no_remat, sp_axis="sp")
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    print(f"model: {args.layers}L/{args.hidden}H "
          f"({n_params / 1e6:.1f}M params), ring of {args.devices}, "
          f"global seq {args.seq_len} "
          f"({args.seq_len // args.devices}/device)")

    opt = FusedAdam(list(model.parameters()), lr=args.lr)

    def lm_loss(logits, tgt):
        return F.cross_entropy(logits.reshape((-1, args.vocab)),
                               tgt.reshape((-1,)))

    step = make_train_step(model, opt, lm_loss,
                           half_dtype=jnp.bfloat16, loss_scale=1.0,
                           axis_name="sp")
    def global_loss_step(state, ids, tgt):
        # each shard's loss covers its local sequence slice; pmean makes
        # the printed number the global mean (grads are already
        # psum-averaged inside the step, so this only fixes monitoring)
        state, loss = step._step_fn(state, ids, tgt)
        return state, jax.lax.pmean(loss, "sp")

    sharded = jax.jit(jax.shard_map(
        global_loss_step, mesh=mesh,
        in_specs=(P(), P(None, "sp"), P(None, "sp")),
        out_specs=(P(), P()), check_vma=False))

    rng = np.random.default_rng(0)

    def batch():
        ids = rng.integers(0, args.vocab, (args.batch, args.seq_len))
        tgt = np.roll(ids, -1, axis=1)      # global next-token shift
        return jnp.asarray(ids), jnp.asarray(tgt)

    ids, tgt = batch()
    t0 = time.perf_counter()
    state, loss = sharded(step.state, ids, tgt)
    print(f"compile+first step: {time.perf_counter() - t0:.1f}s "
          f"loss {float(loss):.4f}")

    seen, t_mark = 0, time.perf_counter()
    for i in range(1, args.steps):
        ids, tgt = batch()
        state, loss = sharded(state, ids, tgt)
        seen += args.batch * args.seq_len
        if i % args.print_freq == 0:
            lv = float(loss)               # fetch = device sync
            dt = time.perf_counter() - t_mark
            print(f"step {i}: loss {lv:.4f}  {seen / dt:.0f} tok/s")
            seen, t_mark = 0, time.perf_counter()
    print("final loss:", float(loss))


if __name__ == "__main__":
    sys.exit(main())
