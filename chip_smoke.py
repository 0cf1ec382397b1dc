"""chip_smoke.py — the quickest proof that apex_tpu still starts on the chip.

    python chip_smoke.py                one TPU chip: device, kernels,
                                        train, serve
    python chip_smoke.py --four-chips   one process over a 4-chip mesh:
                                        the data-parallel path and what
                                        it is compared with, nothing else

Everything runs in this one process (a chip belongs to one process), on
GPT-2-small at its published widths with weights made from ``--seed``.
A phase that fails raises; nothing is caught and carried past.  The last
line of standard output is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``; every other
line above it is information, not a metric.  Without a TPU the script
exits non-zero before any phase and prints no result.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import io
import json
import os
import runpy
import statistics
import sys
import time

T0 = time.perf_counter()


def say(msg: str) -> None:
    print(f"[smoke +{time.perf_counter() - T0:6.1f}s] {msg}", flush=True)


@dataclasses.dataclass(frozen=True)
class Sizes:
    """GPT-2-small at its published widths (apex_tpu/models/gpt.py
    ``gpt2_small``) and the batch / pool sizes of each phase.  The
    script always runs the defaults; rehearsals on the CPU call the
    phases directly with a tiny instance."""
    vocab: int = 50257
    hidden: int = 768
    layers: int = 12
    heads: int = 12
    max_positions: int = 1024
    # kernels: flash attention at (B*H, S, D) = (96, 1024, 64) and the
    # windowed variant at S=2048
    attn_batch: int = 8
    attn_seq: int = 1024
    window_batch: int = 4
    window_seq: int = 2048
    window: int = 256
    # train: batch x seq, steps on one repeated batch
    batch: int = 8
    seq: int = 1024
    train_steps: int = 5
    # serve: pool geometry and the request mix
    num_blocks: int = 2048
    block_size: int = 16
    max_batch: int = 8
    prompt_lo: int = 64
    prompt_hi: int = 512
    shared_prefix: int = 256
    new_tokens: int = 32
    # four chips: global batch and steps
    dp_batch: int = 16
    dp_steps: int = 3

    @property
    def head_dim(self) -> int:
        return self.hidden // self.heads


def _model(sz: Sizes, **kw):
    from apex_tpu.models import GptModel
    return GptModel(vocab_size=sz.vocab, hidden=sz.hidden, layers=sz.layers,
                    heads=sz.heads, max_positions=sz.max_positions, **kw)


def _rel_err(got, want) -> float:
    import jax.numpy as jnp
    got = got.astype(jnp.float32)
    want = want.astype(jnp.float32)
    return float(jnp.max(jnp.abs(got - want))
                 / (jnp.max(jnp.abs(want)) + 1e-6))


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------


def require_tpu(n_chips: int):
    """The device check that gates every phase: a TPU, and as many chips
    as the chosen path needs."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU; JAX found {len(devices)}x "
            f"{devices[0].platform} ({devices[0].device_kind})")
    if len(devices) < n_chips:
        raise SystemExit(
            f"chip_smoke: this path needs {n_chips} chips; JAX found "
            f"{len(devices)}")
    return devices


def phase_device(devices) -> None:
    import jax
    import jax.numpy as jnp
    import jaxlib

    d0 = devices[0]
    try:
        from importlib.metadata import version
        libtpu = version("libtpu")
    except Exception:       # noqa: BLE001 — version string is decoration
        libtpu = "unknown"
    say(f"device: platform={d0.platform} kind={d0.device_kind!r} "
        f"count={len(devices)} jax={jax.__version__} "
        f"jaxlib={jaxlib.__version__} libtpu={libtpu}")

    # The timing idiom, settled once: the same chain of large matmuls,
    # closed by block_until_ready and closed by a scalar fetch.  If
    # block_until_ready waits for the device the two agree; were it a
    # no-op it would report the enqueue time, a small fraction.
    n, links = 4096, 64
    x = jnp.full((n, n), 1.0 / n, jnp.bfloat16)

    @jax.jit
    def chain(a):
        for _ in range(links):
            a = jnp.matmul(a, x)
        return a

    jax.block_until_ready(chain(x))                   # compile + warm

    def timed(close):
        ts = []
        for _ in range(5):
            t0 = time.perf_counter()
            close(chain(x))
            ts.append(time.perf_counter() - t0)
        return statistics.median(ts)

    t_enqueue = timed(lambda out: None)
    jax.block_until_ready(chain(x))                   # drain the queue
    t_block = timed(jax.block_until_ready)
    t_fetch = timed(lambda out: float(jnp.sum(out.astype(jnp.float32))))
    say(f"timing idiom: {links} chained {n}^3 bf16 matmuls — "
        f"enqueue only {t_enqueue * 1e3:.2f} ms, block_until_ready "
        f"{t_block * 1e3:.2f} ms, scalar fetch {t_fetch * 1e3:.2f} ms "
        f"(ratio block/fetch {t_block / t_fetch:.3f})")
    if not 0.8 <= t_block / t_fetch <= 1.25:
        raise AssertionError(
            "block_until_ready and a scalar fetch disagree on the same "
            f"work: {t_block * 1e3:.2f} ms vs {t_fetch * 1e3:.2f} ms")


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

#: flash attention in bf16 against attention_reference in float32, as
#: max|got - want| / max|want|: one bf16 rounding of the output is 2^-9
#: relative (0.002); the backward sums bf16-rounded products over S keys
FLASH_FWD_TOL = 1e-2
FLASH_BWD_TOL = 2e-2


def _flash_case(rng, batch, heads, seq, dim, window, label):
    import jax
    import jax.numpy as jnp

    from apex_tpu.contrib.multihead_attn.attn_funcs import (
        attention_reference, flash_attention)

    scale = dim ** -0.5
    q, k, v, w = (jnp.asarray(rng.standard_normal((batch, heads, seq, dim)),
                              jnp.bfloat16) for _ in range(4))

    # w is an argument, not a closed-over constant: a constant this size
    # is baked into the executable (53 MB per program in the compile cache)
    def flash_loss(q, k, v, w):
        out = flash_attention(q, k, v, causal=True, sliding_window=window)
        return jnp.sum(out.astype(jnp.float32) * w), out

    def ref_loss(q, k, v, w):
        out = attention_reference(q, k, v, None, True, scale, window=window)
        return jnp.sum(out * w), out

    w32 = w.astype(jnp.float32)
    compiled = jax.jit(jax.value_and_grad(
        flash_loss, argnums=(0, 1, 2), has_aux=True)).lower(
            q, k, v, w32).compile()
    n_calls = compiled.as_text().count("tpu_custom_call")
    (_, out), grads = compiled(q, k, v, w32)
    f32 = [a.astype(jnp.float32) for a in (q, k, v)]
    (_, ref_out), ref_grads = jax.jit(jax.value_and_grad(
        ref_loss, argnums=(0, 1, 2), has_aux=True))(*f32, w32)
    errs = {"out": _rel_err(out, ref_out)}
    for name, g, rg in zip(("dq", "dk", "dv"), grads, ref_grads):
        errs[name] = _rel_err(g, rg)
    say(f"kernels: {label} (B*H={batch * heads}, S={seq}, D={dim}, bf16) "
        f"tpu_custom_calls={n_calls} rel err vs float32 reference "
        + " ".join(f"{k_}={e:.2e}" for k_, e in errs.items()))
    if not errs["out"] <= FLASH_FWD_TOL:
        raise AssertionError(f"{label}: forward error {errs['out']:.3e} "
                             f"> {FLASH_FWD_TOL}")
    worst = max(errs["dq"], errs["dk"], errs["dv"])
    if not worst <= FLASH_BWD_TOL:
        raise AssertionError(f"{label}: backward error {worst:.3e} "
                             f"> {FLASH_BWD_TOL}")
    # bf16, no bias, a head within VMEM: the resident pair (a call the
    # tiled kernels take would show three: forward, dq, dkv)
    if n_calls < 2:
        raise AssertionError(
            f"{label}: expected the Pallas forward and backward kernels "
            f"in the compiled program, found {n_calls} tpu_custom_call")


def _fused_optimizer_comparison(rng) -> None:
    """The comparison tests/test_kernels.py makes in interpret mode
    (fused multi-tensor kernel vs the per-bucket XLA path), here in the
    mode the chip compiles — printed for the record (ROADMAP D4), and
    held only to a bound that catches wrong math, not rounding."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from apex_tpu.kernels.multi_tensor import fused_adam, fused_sgd
    from apex_tpu.ops import multi_tensor as ops_mt

    shapes = [(33, 7), (128,), (5, 3, 11), (257,), (768, 3072)]

    def tensors():
        return [jnp.asarray(rng.standard_normal(s), jnp.float32)
                for s in shapes]

    flag = jnp.zeros((), jnp.int32)
    cases = {
        "fused_sgd": (fused_sgd, ops_mt.sgd_unfused,
                      [tensors(), tensors(), tensors()],
                      (0.01, 0.9, 0.0, 0.1, False, False, False, 2.0)),
        "fused_adam": (fused_adam, ops_mt.adam_unfused,
                       [tensors(), tensors(), tensors(),
                        [jnp.abs(t) for t in tensors()]],
                       (1e-3, 0.9, 0.999, 1e-8, 7, 0, True, 0.01)),
    }
    eps = float(np.finfo(np.float32).eps)
    for name, (fused, unfused, lists, args) in cases.items():
        got = jax.jit(lambda f, t: fused(f, t, *args))(flag, lists)
        ref = jax.jit(lambda f, t: unfused(f, t, *args))(flag, lists)
        worst_abs = worst_eps = 0.0
        mismatched = total = 0
        for lr_, lg in zip(ref[1:], got[1:]):
            for r, g in zip(lr_, lg):
                r, g = np.asarray(r), np.asarray(g)
                diff = np.abs(r - g)
                worst_abs = max(worst_abs, float(diff.max()))
                worst_eps = max(worst_eps, float(
                    (diff / (eps * np.maximum(np.abs(r), 1.0))).max()))
                mismatched += int((diff > 0).sum())
                total += diff.size
                np.testing.assert_allclose(g, r, rtol=1e-5, atol=1e-6)
        say(f"kernels: {name} compiled vs per-bucket XLA, float32: "
            f"{mismatched}/{total} elements differ, max abs "
            f"{worst_abs:.3e} = {worst_eps:.2f} x eps*max(|ref|, 1) (the "
            f"interpret-mode test allows 2)")


def phase_kernels(sz: Sizes, seed: int) -> None:
    import numpy as np

    from apex_tpu.kernels import dispatch

    mode = dispatch.pallas_mode()
    say(f"kernels: pallas_mode() = {mode!r}")
    if mode != "compiled":
        raise AssertionError(f"pallas_mode() is {mode!r}, not 'compiled'")
    rng = np.random.default_rng(seed)
    _flash_case(rng, sz.attn_batch, sz.heads, sz.attn_seq, sz.head_dim,
                None, "flash causal")
    _flash_case(rng, sz.window_batch, sz.heads, sz.window_seq, sz.head_dim,
                sz.window, f"flash causal window={sz.window}")
    _fused_optimizer_comparison(rng)


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------


def _lm_step(sz: Sizes, seed: int, model_kw=None, **step_kw):
    """GPT-2-small + FusedAdam + chunked next-token loss under the bf16
    fused step — the step bench.py's ``build_gpt_step`` builds."""
    import jax.numpy as jnp

    import apex_tpu.nn as nn
    from apex_tpu.contrib.xentropy import make_chunked_lm_loss
    from apex_tpu.optimizers import FusedAdam
    from apex_tpu.training import make_train_step

    nn.manual_seed(seed)
    model = _model(sz, attn_dropout=0.0, output_hidden=True,
                   **(model_kw or {}))
    opt = FusedAdam(list(model.parameters()), lr=6e-4, weight_decay=0.1)
    loss_fn = make_chunked_lm_loss(vocab_size=sz.vocab, padding_idx=-1)
    return make_train_step(model, opt, loss_fn, half_dtype=jnp.bfloat16,
                           loss_scale=1.0, **step_kw)


def _ids(sz: Sizes, seed: int, batch: int):
    import jax.numpy as jnp
    import numpy as np
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, sz.vocab, (batch, sz.seq)), jnp.int32)


def phase_train(sz: Sizes, seed: int) -> None:
    import jax
    import numpy as np

    from apex_tpu.observe import registry as obs
    from apex_tpu.runtime import executor, step_cache

    step = _lm_step(sz, seed)
    ids = _ids(sz, seed, sz.batch)
    before = step_cache.kind_stats("train_step")
    flash = {tier: obs.counter(f"kernels.dispatch.flash_attention.{tier}")
             for tier in ("pallas", "xla", "resident", "packed")}
    flash_before = {tier: c.value for tier, c in flash.items()}
    head = {path: obs.counter(f"kernels.dispatch.lm_head_loss.{path}")
            for path in ("grad_with_forward", "checkpointed")}
    head_before = {path: c.value for path, c in head.items()}
    losses, times = [], []
    for _ in range(sz.train_steps):
        t0 = time.perf_counter()
        loss = step(ids, ids)               # through the executor
        jax.block_until_ready(loss)
        times.append(time.perf_counter() - t0)
        losses.append(float(loss))
    after = step_cache.kind_stats("train_step")
    compiles = after["compiles"] - before["compiles"]
    dispatches = after["dispatches"] - before["dispatches"]
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    say(f"train: gpt2_small {sz.batch}x{sz.seq} bf16 FusedAdam, "
        f"{sz.train_steps} steps through step(ids, ids): losses "
        + " ".join(f"{l:.4f}" for l in losses))
    say(f"train: first step (trace + compile + run) {times[0]:.1f} s; "
        f"later steps ms " + " ".join(f"{t * 1e3:.1f}" for t in times[1:])
        + f"; peak_bytes_in_use {peak}")
    say(f"train: compiles={compiles} dispatches={dispatches} "
        f"donation policy={executor.donation.enabled} "
        f"step donates state={step._donate_state}")
    if not all(np.isfinite(losses)):
        raise AssertionError(f"train: non-finite loss in {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"train: loss did not fall: {losses}")
    if compiles != 1 or dispatches != sz.train_steps:
        raise AssertionError(
            f"train: expected 1 compile and {sz.train_steps} dispatches, "
            f"got {compiles} and {dispatches}")
    if not (executor.donation.enabled and step._donate_state):
        raise AssertionError("train: donation did not resolve on")
    # the counters move where the flash rules are applied, at trace time:
    # their change across the step's one trace is the tier the step took,
    # and how many of its Pallas calls took the resident kernels (a whole
    # bf16 sequence in VMEM; every one at these sizes), entered packed on
    # the projection's own output (whole lane rows of heads; every one)
    took = {tier: c.value - flash_before[tier] for tier, c in flash.items()}
    say(f"train: flash_attention tiers traced into the step: {took}")
    if not took["pallas"] or took["xla"]:
        raise AssertionError(
            f"train: the step's attention took {took}, expected the "
            f"Pallas tier alone")
    if min(took["resident"], took["packed"]) < took["pallas"]:
        raise AssertionError(
            f"train: {took['resident']} of the step's {took['pallas']} "
            f"Pallas attention calls took the resident kernels and "
            f"{took['packed']} their packed entry")
    # the factory's loss took its gradient with its forward (one loop
    # over row chunks), and nothing took the checkpointed two
    took = {path: c.value - head_before[path] for path, c in head.items()}
    say(f"train: lm_head_loss paths traced into the step: {took}")
    if took != {"grad_with_forward": 1, "checkpointed": 0}:
        raise AssertionError(
            f"train: the step's LM-head loss took {took}, expected one "
            f"gradient-with-forward pass")


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

#: where the engine and generate() pick different tokens, the float32
#: reference logits of the two picks must be this close, relative to the
#: largest |logit| of that step: two bf16 ulps (2 * 2^-8)
TIE_TOL = 2.0 ** -7


def _requests(sz: Sizes, seed: int):
    """Eight seeded requests with prompts of prompt_lo..prompt_hi tokens.
    Request r0 is a shared prefix plus a tail; r1 is exactly that prefix
    (block-aligned), so once r0 has committed it r1's admission is a
    full-chain prefix hit and forks its last block copy-on-write."""
    import numpy as np

    from apex_tpu.serve import Request

    rng = np.random.default_rng(seed)

    def toks(n):
        return [int(t) for t in rng.integers(1, sz.vocab, n)]

    prefix = toks(sz.shared_prefix)
    lens = [sz.prompt_lo, sz.prompt_hi] + [
        int(n) for n in rng.integers(sz.prompt_lo, sz.prompt_hi + 1, 4)]
    prompts = [prefix + toks(sz.shared_prefix // 2), prefix] \
        + [toks(n) for n in lens]
    return [Request(f"r{i}", p, sz.new_tokens)
            for i, p in enumerate(prompts)]


def _reference_logits(model, ids):
    """Plain float32 logits for ``ids (1, S)``: the served weights
    upcast, the non-cached forward, XLA attention (no kernel)."""
    import jax
    import jax.numpy as jnp

    from apex_tpu.kernels.dispatch import force_mode
    from apex_tpu.nn.modules import Ctx

    objs = list(model.parameters()) + list(model.buffers())

    def fwd(vals, ids):
        ctx = Ctx(env={id(o): v for o, v in zip(objs, vals)},
                  stats_out={}, training=False)
        return model.forward(ctx, ids)

    vals = [o.data.astype(jnp.float32)
            if jnp.issubdtype(o.data.dtype, jnp.floating) else o.data
            for o in objs]
    with force_mode("off"):
        return jax.jit(fwd)(vals, ids)[0]


def _agreement(model, req, served, sz: Sizes) -> str:
    """Engine output vs contiguous-cache ``generate()`` for one request.
    Tokens must agree up to the first near-tie; at a disagreement the
    two picks' float32 reference logits must be within TIE_TOL."""
    import jax.numpy as jnp
    import numpy as np

    from apex_tpu.models.gpt import generate

    prompt = jnp.asarray([req.prompt], jnp.int32)
    full = np.asarray(generate(model, prompt, sz.new_tokens))[0]
    want = [int(t) for t in full[len(req.prompt):]]
    n_same = next((i for i, (a, b) in enumerate(zip(served, want))
                   if a != b), len(want))
    if n_same == len(want):
        return f"{req.rid}: {n_same}/{len(want)} tokens equal generate()"
    ctx_ids = jnp.asarray([list(req.prompt) + want[:n_same]], jnp.int32)
    logits = np.asarray(_reference_logits(model, ctx_ids)[-1], np.float32)
    a, b = served[n_same], want[n_same]
    gap = abs(float(logits[a]) - float(logits[b])) \
        / float(np.max(np.abs(logits)))
    msg = (f"{req.rid}: first {n_same}/{len(want)} tokens equal "
           f"generate(); at step {n_same} engine picked {a}, generate() "
           f"{b}, float32 reference logits {logits[a]:.5f} vs "
           f"{logits[b]:.5f} (gap {gap:.2e} of max |logit|, top-1 "
           f"{int(np.argmax(logits))})")
    if not gap <= TIE_TOL:
        raise AssertionError(f"serve: not a near-tie — {msg}")
    return msg


def phase_serve(sz: Sizes, seed: int) -> None:
    import apex_tpu.nn as nn
    from apex_tpu.runtime import step_cache
    from apex_tpu.serve import ServeEngine, blocks_for, bucket

    nn.manual_seed(seed)
    model = _model(sz, dropout=0.0, attn_dropout=0.0).bfloat16()
    model.eval()
    reqs = _requests(sz, seed)
    longest = max(len(r.prompt) for r in reqs) + sz.new_tokens
    bound = len({bucket(b, sz.max_batch)
                 for b in range(1, sz.max_batch + 1)}) \
        * len({bucket(t) for t in range(
            1, blocks_for(longest, sz.block_size) + 2)})
    before = {k: step_cache.kind_stats(k)["compiles"]
              for k in ("decode_step", "prefill_step")}
    t0 = time.perf_counter()
    with ServeEngine(model, num_blocks=sz.num_blocks,
                     block_size=sz.block_size,
                     max_batch=sz.max_batch) as eng:
        # r0 alone first; the rest arrive once its prompt is ingested,
        # so r1's admission finds the shared prefix committed
        later = len(reqs[0].prompt) // eng.scheduler.prefill_chunk + 1
        out = eng.run(reqs, arrivals=[0] + [later] * (len(reqs) - 1))
        wall = time.perf_counter() - t0
        m = eng.metrics()
        ticks = eng.tick
        donated = eng._donate
    decode_compiles = m["decode"]["compiles"] - before["decode_step"]
    prefill_compiles = m["prefill"]["compiles"] - before["prefill_step"]
    pc = m["prefix_cache"]
    say(f"serve: gpt2_small bf16, pool {sz.num_blocks}x{sz.block_size}, "
        f"max_batch {sz.max_batch}: {len(out)}/{len(reqs)} requests "
        f"finished in {ticks} ticks, {wall:.1f} s wall (compiles "
        f"included); prompts "
        + ",".join(str(len(r.prompt)) for r in reqs)
        + f"; {sz.new_tokens} new tokens each")
    say(f"serve: decode compiles {decode_compiles} (bucket bound {bound}), "
        f"prefill compiles {prefill_compiles}, pool donated={donated}, "
        f"prefix cache tokens saved {pc['prefill_tokens_saved']}, "
        f"cow forks {pc['cow_forks']}, hit rate {pc['hit_rate']:.3f}")
    missing = [r.rid for r in reqs
               if len(out.get(r.rid, ())) != sz.new_tokens]
    if missing:
        raise AssertionError(f"serve: unfinished requests {missing}")
    if not pc["prefill_tokens_saved"] > 0 or not pc["cow_forks"] > 0:
        raise AssertionError(f"serve: no prefix-cache hit / fork: {pc}")
    if not 0 < decode_compiles <= bound:
        raise AssertionError(
            f"serve: decode compiles {decode_compiles} outside (0, {bound}]")
    # r1 was served through the prefix cache and a copy-on-write fork;
    # r2 is the shortest cold prompt
    for req in (reqs[1], reqs[2]):
        say("serve: " + _agreement(model, req, out[req.rid], sz))


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

#: dp=4 against one chip on the same global batch and weights, bf16
#: forward: the per-step losses differ by summation order only (seen on
#: the chip: 1.4e-5 relative at most over three steps)
DP_LOSS_RTOL = 1e-3


def _spans(tree, devices) -> bool:
    import jax
    want = set(devices)
    return all(leaf.sharding.device_set == want
               for leaf in jax.tree.leaves(tree))


def phase_four_chips(sz: Sizes, seed: int, devices) -> None:
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devices = list(devices[:4])
    mesh = Mesh(np.array(devices), ("data",))
    ids = _ids(sz, seed, sz.dp_batch)
    # dp shards draw independent dropout masks; the comparison needs none
    no_dropout = {"model_kw": {"dropout": 0.0}}

    # (a) the comparison first: the same step and global batch on one of
    # the four devices, through the executor
    single = _lm_step(sz, seed, **no_dropout)
    one = [float(single(ids, ids)) for _ in range(sz.dp_steps)]
    say(f"four-chips: one-chip losses, batch {sz.dp_batch}x{sz.seq}: "
        + " ".join(f"{l:.5f}" for l in one))
    del single

    dp = _lm_step(sz, seed, axis_name="data", **no_dropout)

    def body(state, x, y):
        state, loss = dp._step_fn(state, x, y)
        return state, loss[None]            # one local mean per shard

    sharded = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P(), P("data"), P("data")),
        out_specs=(P(), P("data")), check_vma=False), donate_argnums=(0,))
    state = jax.device_put(dp.state, NamedSharding(mesh, P()))
    batch = jax.device_put(ids, NamedSharding(mesh, P("data")))
    if not (_spans(state, devices) and _spans(batch, devices)):
        raise AssertionError("four-chips: state or batch is not placed "
                             "on all four devices")
    compiled = sharded.lower(state, batch, batch).compile()
    text = compiled.as_text()
    four = []
    for _ in range(sz.dp_steps):
        state, shard_losses = compiled(state, batch, batch)
        four.append(float(np.mean(np.asarray(shard_losses))))
    if not (_spans(state, devices) and _spans(shard_losses, devices)):
        raise AssertionError("four-chips: the step's outputs do not span "
                             "all four devices")
    say(f"four-chips: dp=4 losses ({sz.dp_batch // 4} per chip):      "
        + " ".join(f"{l:.5f}" for l in four)
        + f"; all-reduce ops in program: {text.count('all-reduce')}; "
        f"state and batch span {len(devices)} devices")
    if "all-reduce" not in text:
        raise AssertionError("four-chips: no all-reduce in the dp program")
    np.testing.assert_allclose(four, one, rtol=DP_LOSS_RTOL)
    if not four[-1] < four[0]:
        raise AssertionError(f"four-chips: dp loss did not fall: {four}")
    del state, dp

    # (b) SyncBatchNorm + FusedSGD + dynamic loss scale on the real mesh
    import __graft_entry__ as graft
    graft.syncbn_dp_steps(mesh, lambda msg: say("four-chips: " + msg))

    # (c) the simple distributed example, unmodified, in this process
    from apex_tpu.parallel import distributed
    n_mesh = distributed._default_mesh().devices.size
    example = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "examples", "simple", "distributed",
                           "distributed_data_parallel.py")
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        runpy.run_path(example, run_name="__main__")
    lines = [ln for ln in buf.getvalue().splitlines()
             if ln.startswith(("step ", "final loss"))]
    say(f"four-chips: {os.path.relpath(example)} over {n_mesh} devices: "
        + " | ".join(lines))
    first = float(lines[0].split("loss")[-1])
    final = float(lines[-1].split(":")[-1])
    if n_mesh != 4 or not np.isfinite(final) or not final < first:
        raise AssertionError("four-chips: the distributed example did "
                             "not train over four devices")


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the data-parallel path over a 4-chip "
                         "mesh and its one-chip comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = require_tpu(4 if args.four_chips else 1)

    from apex_tpu import compile_cache, runtime
    cache = compile_cache.enable()
    named = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    say(f"compile cache: {cache.directory} (" + (
        "JAX_COMPILATION_CACHE_DIR" if named
        else "fixed path in the checkout") + ")")
    say(f"native host runtime loaded: {runtime.available()}")

    sz = Sizes()
    phase_device(devices)
    if args.four_chips:
        phase_four_chips(sz, args.seed, devices)
    else:
        phase_kernels(sz, args.seed)
        phase_train(sz, args.seed)
        phase_serve(sz, args.seed)
    say(f"compile cache: {cache.hits} hits, {cache.misses} misses")
    d0 = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
