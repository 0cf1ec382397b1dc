"""What a serving kind shares with the others: building the engine from
the configuration's settings, warming the shapes the mix reaches, the
window, its numbers, and the comparison with the reference."""
from __future__ import annotations

from . import cells, correct, serve_loop, sut, traffic, weights
from .runenv import percentile


#: rows of one reference program: requests go through it four at a time
REF_ROWS = 4


def reference_sample(samples, n, seed):
    """The requests the reference goes over: all of them, or, where the
    cell's settings say ``reference_sample: n``, the longest and
    ``n - 1`` others drawn from the seed."""
    if n is None or n >= len(samples):
        return samples
    longest = max(range(len(samples)),
                  key=lambda i: len(samples[i][0]) + len(samples[i][1]))
    others = [i for i in range(len(samples)) if i != longest]
    rng = traffic.rng_for(seed, "reference_sample")
    keep = sorted([longest] + [others[i] for i in rng.permutation(
        len(others))[:max(0, n - 1)]])
    return [samples[i] for i in keep]


def served_gaps(cell, seed, samples, control=None):
    """``(gaps, margins)``, one entry for every served token of every
    request ``(prompt, served)`` of ``samples``: the gap by which the
    served token's float32 reference logit lies below the reference's
    best at that position (0 where it is the reference's own choice),
    and the margin of the reference's best over its second best there.
    With ``control`` the tokens judged are the lower-precision
    reference's own first choices at the same positions of the same
    prompts and tokens."""
    import jax.numpy as jnp
    import numpy as np
    cfg, family = cell.config, cell.family
    # the benchmark's leaves as they are served; the reference widens
    # them itself, all at once or layer by layer
    w = weights.make_weights(family, cfg, seed, cfg["serve"]["weights_dtype"])
    served_token_gaps = cell.reference.served_token_gaps
    s_max = family.max_positions(cfg)
    gaps, margins = [], []
    for r in range(0, len(samples), REF_ROWS):
        block = samples[r:r + REF_ROWS]
        padded = np.zeros((REF_ROWS, s_max), np.int32)
        picked = np.zeros((REF_ROWS, s_max), np.int32)
        spans = []
        for i, (prompt, served) in enumerate(block):
            ids = (list(prompt) + list(served))[:s_max]
            n_p, n_out = len(prompt), len(ids) - len(prompt)
            padded[i, :len(ids)] = ids
            picked[i, n_p - 1:n_p - 1 + n_out] = ids[n_p:]
            spans.append((n_p - 1, n_p - 1 + n_out))
        g, m = (np.asarray(x) for x in served_token_gaps(
            cfg, w, jnp.asarray(padded), jnp.asarray(picked), control))
        gaps += [g[i, a:b] for i, (a, b) in enumerate(spans)]
        margins += [m[i, a:b] for i, (a, b) in enumerate(spans)]
    if not gaps:
        return np.zeros(0, np.float32), np.zeros(0, np.float32)
    return np.concatenate(gaps), np.concatenate(margins)


#: a call is close where the reference's best leads its second best by
#: less than this: twice the widest gap a bf16 logit's rounding opens
CLOSE_CALL = 0.05


def gap_numbers(gaps, margins) -> dict:
    """The number compared, from a run's per-token gaps and margins:
    the summed squared gap over the number of close calls.

    How many calls are close differs ten-fold from seed to seed (the
    model's weights are random), and only at a close call can rounding
    change the token, so the gaps are set against their number.  Under
    logit noise of size s the sum of gaps grows as s**2 and the sum of
    their squares as s**3; the squares weigh the wide gaps, which a
    precision below the stated one opens and the stated one cannot
    (PERF.md section 4 has the readings of both)."""
    import numpy as np
    if not len(gaps):
        return {"served_sq_gap_per_close_call": float("nan")}
    close = max(1, int((margins < CLOSE_CALL).sum()))
    return {"served_sq_gap_per_close_call":
            float(np.square(gaps, dtype=np.float64).sum() / close)}


def gap_report(gaps, margins) -> str:
    if not len(gaps):
        return "no served token"
    close = int((margins < CLOSE_CALL).sum())
    return (f"{int((gaps > 0).sum())} tokens are not the reference's "
            f"choice, {close} calls are close (margin under {CLOSE_CALL}); "
            f"gap widest {gaps.max():.5f}, mean {gaps.mean():.3e}, summed "
            f"over close calls {gaps.sum() / max(1, close):.3e}")


def tick_report(ticks, window: float) -> str:
    """Where the window's time went by the host's clock: the ticks'
    lengths, and what lay between them (the harness's own bookkeeping).
    A run that reads low names its cause here."""
    if not ticks:
        return "ticks: none"
    lens = [(tk["t1"] - tk["t0"]) * 1e3 for tk in ticks]
    between = sum(b["t0"] - a["t1"] for a, b in zip(ticks, ticks[1:]))
    longest = max(range(len(ticks)), key=lens.__getitem__)
    return (f"ticks: {len(ticks)}, p50 {percentile(lens, 50):.2f} p99 "
            f"{percentile(lens, 99):.2f} max {lens[longest]:.2f} ms (tick "
            f"{longest}: {'+'.join(ticks[longest].get('dispatches', []))}); "
            f"the five longest sum to "
            f"{sum(sorted(lens)[-5:]):.1f} ms; between ticks "
            f"{between * 1e3:.1f} ms of {window * 1e3:.0f}")


def run(cell, args, env, lead_in, drive, fault, eng=None):
    """``eng``: an engine that is built and warm already (the readings
    tool serves many seeds from one, swapping the weights); it is then
    left open and the reference runs beside it."""
    import gc

    import jax
    cfg, mix, st = cell.config, cell.traffic, cell.settings
    sv = cfg["serve"]
    own = eng is None
    if own:
        env.say("engine settings: " + ", ".join(
            f"{k}={sv[k]}" for k in (
                "weights_dtype", "cache_dtype", "block_size", "num_blocks",
                "max_batch", "prefill_chunk", "prefix_cache", "draft")))
        eng = sut.build_engine(cell.family, cfg, args.seed)
    else:
        eng.results.clear()             # request ids repeat from seed to seed
    loop = serve_loop.Loop(eng, env)
    if fault is not None:
        fault(loop)
    if own:
        t = env.now()
        ticks = serve_loop.warm_waves(
            loop, mix, cell.family.vocab(cfg), sv["max_batch"],
            traffic.rng_for(args.seed, "warm"))
        env.say(f"warm-up: prompt lengths {mix['warm_prompt_lens']} in "
                f"waves, {mix.get('warm_prefill_lens', [])} prefill only; "
                f"{ticks} ticks, {env.now() - t:.1f} s; compile cache so "
                f"far {env.cache.hits} hits, {env.cache.misses} misses")
    lead_in(loop)
    loop.finished.clear()
    loop.ticks.clear()
    seconds = args.seconds
    out = {"counters": {}}
    if args.trace:
        # an untraced window of the same length first: the difference is
        # what tracing costs
        seconds = min(args.seconds, st["trace_seconds"])
        t0 = env.now()
        _, t1 = drive(loop, seconds, t0)
        plain = sum(tk["new_tokens"] for tk in loop.ticks) / (t1 - t0), \
            len(loop.ticks) / (t1 - t0)
        loop.finished.clear()
        loop.ticks.clear()
    # the collector's pauses inside the window, timed: a run that reads
    # low may name them
    pauses, began = [], [0.0]

    def on_gc(phase, info):
        if phase == "start":
            began[0] = env.now()
        else:
            pauses.append((info["generation"], env.now() - began[0]))
    gc.callbacks.append(on_gc)
    setup_s = env.since_start()
    t0 = env.now()
    if args.trace:
        env.start_trace()
        t0 = env.now()
    tracked, t1 = drive(loop, seconds, t0)
    window = t1 - t0
    gc.callbacks.remove(on_gc)
    if args.trace:
        out["trace"] = env.stop_trace(window)
        env.say(f"tracing overhead: untraced {plain[0]:.1f} tokens/s, "
                f"{plain[1]:.2f} ticks/s; traced "
                f"{sum(tk['new_tokens'] for tk in loop.ticks) / window:.1f}"
                f" tokens/s, {len(loop.ticks) / window:.2f} ticks/s")
        # serve on, untraced, for the rest of --seconds: the comparison
        # below then reads as many tokens as an untraced run's does (its
        # number scatters three times as widely over a fifth of them)
        rest = args.seconds - 2 * seconds
        if rest > 0:
            seen = {id(tr) for tr in tracked}
            tracked += [tr for tr in drive(loop, rest, env.now())[0]
                        if id(tr) not in seen]
    ticks_w = [tk for tk in loop.ticks if t0 <= tk["t0"] < t1]
    peak = env.memory_peak(cell.chips)

    counted = [tr for tr in tracked if tr.counted]
    finished = [tr for tr in counted if tr.done]
    wrong = [tr for tr in finished
             if len(loop.served(tr)) != tr.max_new]
    failed = len(wrong)
    tokens = sum(sum(1 for x in tr.token_times if t0 <= x < t1 + 1e-9)
                 for tr in tracked)
    itl = [(b - a) * 1e3 for tr in counted
           for a, b in zip(tr.token_times, tr.token_times[1:])]
    e2e = {"setup_s": setup_s, "serve_tokens_per_s": tokens / window}
    env.say(f"closed loop: submitted {len(counted)}, finished "
            f"{len(finished)}, failed {failed}")
    dec = [tk["decode_batch"] for tk in ticks_w
           if "decode_step" in tk["dispatches"]]
    env.say(f"window {window:.4f} s, {tokens} tokens = "
            f"{tokens / window:.2f} tokens/s; itl p50 "
            f"{percentile(itl, 50):.1f} p99 {percentile(itl, 99):.1f} ms "
            f"over {len(itl)}; decode batch mean "
            f"{sum(dec) / len(dec) if dec else float('nan'):.2f}; compiles in "
            f"window {sum(tk['compiles'] for tk in ticks_w)}; bytes in use "
            f"now {peak['in_use']} (peak {peak['peak_in_use']}) + reserved "
            f"peak {peak['reserved']}")
    env.say(tick_report(ticks_w, window) + f"; collector pauses "
            f"{len(pauses)}, {sum(d for _, d in pauses) * 1e3:.1f} ms, "
            f"longest {max([d for _, d in pauses] or [0.0]) * 1e3:.1f} ms")
    if not args.trace:
        # a traced run's span readers print this themselves
        tree = cells._module_at(cell.repo, "readers", "spans") \
            .longest_tick_line(ticks_w)
        env.say(f"longest tick by the program's spans: {tree}")
    out["counters"].update({
        "chips": cell.chips, "ticks": ticks_w,
        "compiles_in_window": sum(tk["compiles"] for tk in ticks_w),
        "dispatch_log": [k for tk in ticks_w for k in tk["dispatches"]],
        "module_prefixes": ["jit_fn("],
        "tokens_per_s": tokens / window,
    })
    # every token served to the window's requests is compared, or to a
    # seeded sample of them: the finished answers whole, the others as
    # far as they got
    served = [(tr.prompt, loop.served(tr)) for tr in counted
              if tr.token_times]
    samples = reference_sample(served, st.get("reference_sample"),
                               args.seed)
    out["samples"] = samples
    if own:
        eng.close()
        del eng, loop.eng                      # free the pool and weights
        loop.live.clear()
        jax.clear_caches()
    else:
        eng.close()         # drops what is live and queued; stays usable
    t = env.now()
    gaps, margins = served_gaps(cell, args.seed, samples)
    out["gaps"], out["margins"] = gaps, margins
    env.say(f"correct: {len(samples)} of {len(served)} request(s), "
            f"{len(finished)} finished in all, {len(gaps)} served tokens "
            f"against the float32 reference in {env.now() - t:.1f} s; "
            f"{len(wrong)} of "
            f"{len(finished)} answers of the wrong length; "
            + gap_report(gaps, margins))
    numbers = dict(gap_numbers(gaps, margins),
                   wrong_length=float(len(wrong)))
    ok, compared = correct.judge(numbers, st["limits"])
    out.update({"correct": ok, "compared": compared,
                "attempted": len(counted), "failed": failed, "peak": peak,
                "end_to_end": e2e, "window_s": window})
    return out
