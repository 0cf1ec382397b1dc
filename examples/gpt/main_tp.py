"""GPT pretraining with data × tensor parallelism — the Megatron recipe
on a 2-D mesh: the batch shards over 'data', attention heads and the MLP
hidden width shard over 'tp' (models/gpt.py ``tp_axis``; one psum per
column→row pair via the f/g conjugate operators,
parallel/tensor_parallel.py).  Weights stay full-size and replicated —
each device slices its head/feature block at trace time — so checkpoints
are shard-count-independent.

The reference has no model parallelism (SURVEY.md §2.3 — its distributed
scope is DDP); this is the TPU-native equivalent of what Megatron-LM
layers on top of it.  Uses the first ``--dp * --tp`` devices JAX finds and
exits with a message when there are fewer (examples/README.md has the
virtual-mesh recipe for a host without that many).

Run: ``python main_tp.py --dp 2 --tp 4 --steps 20``
"""
import argparse
import sys
import time


def parse_args():
    p = argparse.ArgumentParser(
        description="data x tensor parallel GPT pretrain + apex_tpu")
    p.add_argument("--dp", type=int, default=2, help="data-parallel width")
    p.add_argument("--tp", type=int, default=4,
                   help="tensor-parallel width (must divide --heads)")
    p.add_argument("--batch", type=int, default=4,
                   help="GLOBAL batch (shards over --dp)")
    p.add_argument("--seq-len", type=int, default=128)
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--lr", type=float, default=6e-4)
    p.add_argument("--layers", type=int, default=4)
    p.add_argument("--hidden", type=int, default=128)
    p.add_argument("--heads", type=int, default=8)
    p.add_argument("--vocab", type=int, default=50257)
    p.add_argument("--print-freq", type=int, default=5)
    return p.parse_args()


def main():
    args = parse_args()
    n_dev = args.dp * args.tp

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    import apex_tpu.nn as nn
    from apex_tpu.models import GptModel
    from apex_tpu.nn import functional as F
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.training import make_train_step

    devices = jax.devices()[:n_dev]
    if len(devices) < n_dev:
        raise SystemExit(f"need {n_dev} devices, have {len(devices)}")
    if args.heads % args.tp:
        raise SystemExit("--heads must divide by --tp")
    if args.batch % args.dp:
        raise SystemExit("--batch must divide by --dp")
    mesh = Mesh(np.array(devices).reshape(args.dp, args.tp),
                ("data", "tp"))

    nn.manual_seed(0)
    # attn_dropout composes with tp_axis since the in-kernel hash-mask
    # dropout (per-shard seed streams) — 0.1 here exercises it
    model = GptModel(vocab_size=args.vocab, hidden=args.hidden,
                     layers=args.layers, heads=args.heads,
                     max_positions=args.seq_len, attn_dropout=0.1,
                     tp_axis="tp")
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    print(f"model: {args.layers}L/{args.hidden}H "
          f"({n_params / 1e6:.1f}M params), mesh {args.dp}x{args.tp} "
          f"(data x tp), heads {args.heads} -> "
          f"{args.heads // args.tp}/device")

    opt = FusedAdam(list(model.parameters()), lr=args.lr)

    def lm_loss(logits, tgt):
        return F.cross_entropy(logits.reshape((-1, args.vocab)),
                               tgt.reshape((-1,)))

    step = make_train_step(model, opt, lm_loss,
                           half_dtype=jnp.bfloat16, loss_scale=1.0,
                           axis_name="data", tp_axis="tp")

    def global_loss_step(state, ids, tgt):
        # the in-step loss is one data-shard's mean (replicated over tp);
        # pmean over 'data' makes the printed number the global mean
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
    return 0


if __name__ == "__main__":
    sys.exit(main())
