"""Traffic kind ``train``: the fused train step fed a new host batch
every step through the library's own input path, for ``--seconds``.

Set-up builds ONE step object from the seed, drives it through its first
three steps by the window's own call and feed (recording what the
reference is compared with), and hands that same object to the window.
"""
from __future__ import annotations

import collections

from pb import correct, sut, traffic, weights

CHECK_STEPS = 3


def tiny(mix: dict, limits: dict) -> tuple:
    """A mix of this kind and its cell's limits at sizes a CPU test can
    hold (``tests/perfbench/pb_tiny.py``).  The committed limits are the
    chip's, set from readings at the cells' own sizes; at these the
    bf16 step on the CPU reads losses to 2e-5, the first gradient to
    3e-3 (median leaf 4e-4) and the change to 1e-2 (median leaf 4e-4)."""
    mix = dict(mix, global_batch=8, seq_len=32, reference_block_rows=4)
    limits = {"loss1_gap": 5e-4, "loss2_gap": 5e-4, "loss3_gap": 5e-4,
              "grad1_gap": 0.02, "grad1_median_gap": 1e-3,
              "delta3_gap": 0.05, "delta3_median_gap": 1e-3}
    return mix, limits


def _leaf_norm_fn():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def norms(leaves):
        return [jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))
                for a in leaves]
    return norms


def _delta_norm_fn(family, cfg, seed, dtype="float32"):
    """Per-leaf ``||p - p0||`` with ``p0`` redrawn from the seed inside
    the same program, so no second copy of the weights is kept."""
    import jax
    import jax.numpy as jnp
    convert = family.to_program(cfg)

    @jax.jit
    def norms(key, leaves):
        p0 = convert(family.draw(cfg, key, jnp.dtype(dtype)))
        return [jnp.sqrt(jnp.sum(jnp.square(a - b)))
                for a, b in zip(leaves, p0)]
    return lambda leaves: norms(weights.seed_key(seed), leaves)


def drive_first_steps(cell, seed, step, feed_iter, step_call=None):
    """The program's readings over its first CHECK_STEPS steps."""
    import jax
    cfg, family = cell.config, cell.family
    names = family.program_leaf_names(cfg)
    b1 = cfg["train"]["betas"][0]
    norm_fn = _leaf_norm_fn()
    call = step_call or (lambda x, y: step(x, y))
    losses, g1 = [], None
    for i in range(CHECK_STEPS):
        x, y = next(feed_iter)
        losses.append(float(call(x, y)))
        if i == 0:
            m = jax.device_get(norm_fn(sut.adam_first_moment(step)))
            g1 = {n: float(v) / (1.0 - b1) for n, v in zip(names, m)}
    d = jax.device_get(
        _delta_norm_fn(family, cfg, seed)(sut.master_params(step)))
    return {"losses": losses, "grad1_norms": g1,
            "delta_norms": {n: float(v) for n, v in zip(names, d)}}


def reference_readings(cell, seed, quant=None):
    """The same steps by the configuration's plain reference (with
    ``quant``, by the control: one precision down)."""
    import jax.numpy as jnp
    cfg, mix, family = cell.config, cell.traffic, cell.family
    w0 = weights.make_weights(family, cfg, seed, "float32")
    batches = [jnp.asarray(b) for b in traffic.first_train_batches(
        mix, seed, family.vocab(cfg), CHECK_STEPS)]
    return cell.reference.train_reference(
        cfg, w0, batches, block_rows=mix.get("reference_block_rows", 4),
        quant=quant)


def _window(step, feed_iter, seconds, in_flight, env):
    import jax
    pending = collections.deque()
    n = 0
    t0 = env.now()
    while True:
        x, y = next(feed_iter)
        loss = step(x, y)
        n += 1
        pending.append(loss)
        if len(pending) > in_flight:
            pending.popleft().block_until_ready()
        if env.now() - t0 >= seconds:
            break
    jax.block_until_ready(loss)
    return n, env.now() - t0


def run(cell, args, env, step_call_wrapper=None):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    cfg, mix, st = cell.config, cell.traffic, cell.settings
    devices = list(env.devices[:cell.chips])
    parallel = mix["parallel"]
    kind = sut.train_kind(parallel)
    env.say(f"train: {cell.config_name} {mix['global_batch']} x "
            f"{mix['seq_len']} tokens a step, parallel={parallel} over "
            f"{len(devices)} chip(s), in flight {mix['in_flight']}")
    step, mesh = sut.build_train_step(cell.family, cfg, args.seed, parallel,
                                      devices)
    where = NamedSharding(mesh, P("data")) if mesh is not None \
        else devices[0]
    feed = sut.train_feed(
        traffic.train_batches(mix, args.seed, cell.family.vocab(cfg)), where)
    feed_iter = iter(feed)
    call = step_call_wrapper(step) if step_call_wrapper else None
    try:
        prog = drive_first_steps(cell, args.seed, step, feed_iter, call)
        env.say("train: first losses " + " ".join(
            f"{l:.5f}" for l in prog["losses"]))
        call = call or step
        for _ in range(mix.get("warm_steps", 2)):      # fill the pipeline
            x, y = next(feed_iter)
            jax.block_until_ready(call(x, y))
        before = sut.kind_stats(kind)
        tokens_a_step = mix["global_batch"] * mix["seq_len"]
        out = {"counters": {}}
        if args.trace:
            secs = min(args.seconds, st["trace_seconds"])
            n0, w0 = _window(call, feed_iter, secs, mix["in_flight"], env)
            setup_s = env.since_start()
            env.start_trace()
            n, window = _window(call, feed_iter, secs, mix["in_flight"],
                                env)
            out["trace"] = env.stop_trace(window)
            env.say(f"tracing overhead: untraced {n0 * tokens_a_step / w0:.1f}"
                    f" tokens/s, traced {n * tokens_a_step / window:.1f}")
            before["dispatches"] += n0      # count the traced window only
        else:
            setup_s = env.since_start()
            n, window = _window(call, feed_iter, args.seconds,
                                mix["in_flight"], env)
        after = sut.kind_stats(kind)
    finally:
        feed.close()
    peak = env.memory_peak(cell.chips)
    rate = n * tokens_a_step / window
    env.say(f"train: {n} steps, {n * tokens_a_step} tokens in "
            f"{window:.4f} s = {rate:.1f} tokens/s; compiles in window "
            f"{after['compiles'] - before['compiles']}; bytes in use now "
            f"{peak['in_use']} (peak {peak['peak_in_use']}) + reserved "
            f"peak {peak['reserved']}")
    del step, call, feed, feed_iter            # free the program's state
    ref = reference_readings(cell, args.seed)
    numbers = correct.train_numbers(prog, ref)
    env.say(f"correct: reference losses " + " ".join(
        f"{l:.5f}" for l in ref["losses"]) + f"; worst gradient leaf "
        f"{numbers['_grad1_leaf']}, worst change leaf "
        f"{numbers['_delta3_leaf']}, leaves left out of the change "
        f"{numbers['_still_leaves']}")
    ok, compared = correct.judge(numbers, st["limits"])
    out.update({
        "correct": ok, "compared": compared, "attempted": n, "failed": 0,
        "peak": peak,
        "end_to_end": {"train_tokens_per_s": rate, "setup_s": setup_s},
        "window_s": window,
    })
    out["counters"].update({
        "steps": n, "tokens": n * tokens_a_step, "chips": cell.chips,
        "compiles_in_window": after["compiles"] - before["compiles"],
        "dispatch_log": [kind] * n,
        "module_prefixes": ["jit_"],
        "tokens_per_s": rate,
    })
    return out
