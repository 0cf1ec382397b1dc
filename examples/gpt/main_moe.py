"""Switch-MoE GPT pretraining — every second block routes its FFN over
one expert per device along the data axis (models/gpt.py ``moe_axis``;
parallel/expert_parallel.py carries the all_to_all dispatch/combine and
the load-balancing aux loss, which flows through ``Ctx.add_aux_loss``
into the fused step's optimized loss).

The canonical Switch layout: experts ride the SAME mesh axis the batch
shards over, so expert-parallel capacity grows with data parallelism and
the ordinary psum-mean of the step yields exact expert gradients.  The
reference has no MoE (SURVEY.md §2.3).  Uses the first ``--devices``
devices JAX finds and exits with a message when there are fewer
(examples/README.md has the virtual-mesh recipe).

Run: ``python main_moe.py --devices 4 --steps 20 --top-k 1``
"""
import argparse
import sys
import time


def parse_args():
    p = argparse.ArgumentParser(description="Switch-MoE GPT + apex_tpu")
    p.add_argument("--devices", type=int, default=4,
                   help="data-axis width = expert count")
    p.add_argument("--batch", type=int, default=8,
                   help="GLOBAL batch (shards over the axis)")
    p.add_argument("--seq-len", type=int, default=128)
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--lr", type=float, default=6e-4)
    p.add_argument("--layers", type=int, default=4)
    p.add_argument("--hidden", type=int, default=128)
    p.add_argument("--heads", type=int, default=8)
    p.add_argument("--vocab", type=int, default=50257)
    p.add_argument("--top-k", type=int, default=1, choices=(1, 2))
    p.add_argument("--capacity-factor", type=float, default=1.25)
    p.add_argument("--aux-weight", type=float, default=0.01)
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
    from apex_tpu.models.gpt import MoeGptBlock
    from apex_tpu.nn import functional as F
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.training import make_train_step

    devices = jax.devices()[:args.devices]
    if len(devices) < args.devices:
        raise SystemExit(f"need {args.devices} devices, have {len(devices)}")
    if args.batch % args.devices:
        raise SystemExit("--batch must divide by --devices")
    mesh = Mesh(np.array(devices), ("data",))

    nn.manual_seed(0)
    model = GptModel(vocab_size=args.vocab, hidden=args.hidden,
                     layers=args.layers, heads=args.heads,
                     max_positions=args.seq_len, attn_dropout=0.0,
                     moe_axis="data", moe_num_experts=args.devices,
                     moe_top_k=args.top_k,
                     moe_capacity_factor=args.capacity_factor,
                     moe_aux_weight=args.aux_weight)
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    n_moe = sum(1 for blk in model.blocks
                if isinstance(blk, MoeGptBlock))
    print(f"model: {args.layers}L/{args.hidden}H "
          f"({n_params / 1e6:.1f}M params incl. {args.devices} experts "
          f"x {n_moe} MoE blocks, top-{args.top_k})")

    opt = FusedAdam(list(model.parameters()), lr=args.lr)

    def lm_loss(logits, tgt):
        return F.cross_entropy(logits.reshape((-1, args.vocab)),
                               tgt.reshape((-1,)))

    step = make_train_step(model, opt, lm_loss,
                           half_dtype=jnp.bfloat16, loss_scale=1.0,
                           axis_name="data")

    def global_loss_step(state, ids, tgt):
        state, loss = step._step_fn(state, ids, tgt)
        return state, jax.lax.pmean(loss, "data")

    sharded = jax.jit(jax.shard_map(
        global_loss_step, mesh=mesh,
        in_specs=(P(), P("data"), P("data")),
        out_specs=(P(), P()), check_vma=False))

    rng = np.random.default_rng(0)

    def batch():
        ids = rng.integers(0, args.vocab, (args.batch, args.seq_len))
        tgt = np.roll(ids, -1, axis=1)
        return jnp.asarray(ids), jnp.asarray(tgt)

    ids, tgt = batch()
    t0 = time.perf_counter()
    state, loss = sharded(step.state, ids, tgt)
    print(f"compile+first step: {time.perf_counter() - t0:.1f}s "
          f"loss {float(loss):.4f} (incl. aux)")

    seen, t_mark = 0, time.perf_counter()
    for i in range(1, args.steps):
        ids, tgt = batch()
        state, loss = sharded(state, ids, tgt)
        seen += args.batch * args.seq_len
        if i % args.print_freq == 0:
            lv = float(loss)
            dt = time.perf_counter() - t_mark
            print(f"step {i}: loss {lv:.4f}  {seen / dt:.0f} tok/s")
            seen, t_mark = 0, time.perf_counter()
    print("final loss:", float(loss))
    return 0


if __name__ == "__main__":
    sys.exit(main())
