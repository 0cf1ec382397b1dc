"""The latent-attention mixture-of-experts family of the benchmark
(``perfbench/families/latent_moe.py``, its reference, the readers of the
program's routed-expert counters): counts against hand counts, each new
metric's reader fed a synthetic trace, and the near-tie rule of the
reference."""
import json
import os

import numpy as np
import pytest

import pb_tiny
from pb import cells, correct, peaks, serve_common

import run as pbrun

CELL = "gigachat3-serve-decode"
FAMILY = cells.family_module("latent_moe")
with open(os.path.join(pb_tiny.BENCH, "configs",
                       "gigachat3.1-702b-a36b-ep16.json")) as _f:
    CONFIG = json.load(_f)


@pytest.fixture(scope="module")
def repo(tmp_path_factory):
    yield pb_tiny.make_repo(tmp_path_factory.mktemp("pb_latent"))
    from apex_tpu.runtime import step_cache
    step_cache.clear()


# -- counts ------------------------------------------------------------------


def test_parameter_counts_are_the_issues():
    """ISSUE 29's own arithmetic at the published widths."""
    cfg = CONFIG
    # MLA: 7168x1536 + 1536x64x192 + 7168x576 + 512x64x320 + 64x192x7168
    assert FAMILY.attn_params(cfg) == 11010048 + 18874368 + 4128768 \
        + 10485760 + 88080384 == 132579328
    assert FAMILY.expert_params(cfg) == 3 * 7168 * 2048 == 44040192
    norms = 5 * (2 * 7168 + 1536 + 512) + 7168
    want = 2 * 16032 * 7168 + 5 * 132579328 + 3 * 7168 * 18432 \
        + 4 * (17 * 44040192 + 256 * 7168 + 256) + norms
    assert FAMILY.total_params(cfg) == want
    assert 4.290e9 < want < 4.292e9                      # 4.291 G, 8.58 GB
    assert FAMILY.latent_bytes_per_token(cfg) == 5 * 576 * 2 == 5760


TICK = {"decode_batch": 128, "kv_tokens": 200_000, "dispatches":
        ["decode_step"], "moe_pairs": 250, "moe_experts_hit": 61}


@pytest.mark.parametrize("count,want", [
    # one query a session over its live rows: a score against 576 and
    # an output of 512 a head, 64 heads, 5 layers
    ("latent_attn_decode_flops", 5 * 200_000 * 2 * 64 * (576 + 512)),
    # rows once at 576 x 2 B; a session's 64 queries of 576 bf16 in and
    # 64 outputs of 512 float32 out, a layer
    ("latent_attn_decode_bytes",
     5 * (200_000 * 1152 + 128 * 64 * (1152 + 2048))),
    ("routed_experts_flops", 250 * 2 * 44040192),
    # the 61 experts hit once; a pair: 7168 in, 4096 out, 2048 in, 7168 out
    ("routed_experts_bytes",
     2 * (61 * 44040192 + 250 * (2 * 7168 + 3 * 2048))),
])
def test_kernel_counts_against_hand_counts(count, want):
    assert getattr(FAMILY, count)(CONFIG, TICK) == want


def test_step_counts_against_hand_counts():
    cfg = CONFIG
    dense = 5 * 132579328 + 3 * 7168 * 18432 \
        + 4 * (44040192 + 256 * 7168) + 16032 * 7168
    assert FAMILY.dense_params(cfg) == dense
    assert FAMILY.decode_step_flops(cfg, TICK) == \
        2 * dense * 128 + 2 * 44040192 * 250 \
        + 5 * 200_000 * 2 * 64 * (576 + 512)
    # every parameter but the embedding's rows (128 are read) and the 3
    # held experts of 64 that no token went to; rows read and written
    weights = FAMILY.total_params(cfg) - 16032 * 7168 - 3 * 44040192
    assert FAMILY.decode_step_bytes(cfg, TICK) == \
        2 * (weights + 128 * 7168) + 5760 * (200_000 + 128)
    # without the program's counters: the router's average, every expert
    bare = {k: v for k, v in TICK.items() if not k.startswith("moe_")}
    assert FAMILY.routed_experts_flops(cfg, bare) == \
        2 * 44040192 * 128 * 4 * 8 * 16 / 256
    assert FAMILY.decode_step_bytes(cfg, bare) == \
        2 * (FAMILY.total_params(cfg) - 16032 * 7168 + 128 * 7168) \
        + 5760 * (200_000 + 128)
    # least time of a step at the cell's load: the issue's 12 ms
    t = FAMILY.decode_step_bytes(cfg, dict(bare, kv_tokens=128 * 1660)) \
        / 819e9
    assert 0.0115 < t < 0.0125


# -- the readers, fed a synthetic trace ------------------------------------------


def _ctx(ticks, ops, records):
    return {"cfg": CONFIG, "family": FAMILY,
            "peaks": peaks.PEAKS["TPU v5 lite"],
            "counters": {"ticks": ticks},
            "trace": {"ops": ops}, "span_records": records,
            "span_children": {}}


def _tick(i, moe=None, kinds=("decode_step",)):
    tk = {"t0": 1.0 + i, "t1": 1.5 + i, "dispatches": list(kinds),
          "decode_batch": 128, "kv_tokens": 200_000}
    rec = {"span": "serve.step", "id": i, "parent": None,
           "t0_ns": int((1.1 + i) * 1e9), "t1_ns": int((1.4 + i) * 1e9)}
    rec.update(moe or {})
    return tk, rec


MOE = {"moe_pairs": 250, "moe_experts_hit": 61, "moe_pairs_max": 12,
       "moe_layers": 4, "moe_held": 16}


@pytest.mark.parametrize("metric,want", [
    ("moe_pairs_per_step", (250 + 262) / 2),
    # max over mean a cell: 12 / (250 / 64) and 8 / (262 / 64), averaged
    ("moe_load_max_over_mean", (12 * 64 / 250 + 8 * 64 / 262) / 2),
])
def test_counter_readers_on_synthetic_records(metric, want):
    pairs = [_tick(0, MOE), _tick(1, dict(MOE, moe_pairs=262,
                                           moe_pairs_max=8)),
             _tick(2, MOE, kinds=("prefill_step",))]
    ctx = _ctx([p[0] for p in pairs], [], [p[1] for p in pairs])
    reader, kw = cells.metric_reader(metric)
    assert reader(ctx, **kw) == pytest.approx(want, rel=1e-12)
    # a program that keeps no such counters: nothing to read, no error
    bare = [_tick(i) for i in range(2)]
    assert reader(_ctx([p[0] for p in bare], [], [p[1] for p in bare]),
                  **kw) is None
    assert reader(_ctx([p[0] for p in bare], [], []), **kw) is None


@pytest.mark.parametrize("metric,op,count_bytes", [
    ("latent_attn_decode_roofline", "latent_attention_decode",
     "latent_attn_decode_bytes"),
    ("routed_experts_roofline", "routed_experts", "routed_experts_bytes"),
])
def test_kernel_rooflines_on_a_synthetic_trace(metric, op, count_bytes):
    """Two decode ticks; the trace shows the kernel's operations for 20
    ms in all, and another operation that is none of its business.  Both
    kernels are memory-bound by their counts, so the share is the bytes
    over 819 GB/s over those 20 ms."""
    pairs = [_tick(0, MOE), _tick(1, MOE)]
    ops = [(f"%{op}.{i} = bf16[8] custom-call()", 0, 5_000_000)
           for i in range(4)] + [("%fusion.7 = f32[8] fusion()", 0, 9e9)]
    ctx = _ctx([p[0] for p in pairs], ops, [p[1] for p in pairs])
    reader, kw = cells.metric_reader(metric)
    least = 2 * getattr(FAMILY, count_bytes)(CONFIG, dict(pairs[0][0], **MOE)) \
        / 819e9
    assert reader(ctx, **kw) == pytest.approx(100 * least / 0.020, rel=1e-9)
    assert 0 < reader(ctx, **kw) < 100
    assert reader(_ctx([p[0] for p in pairs], ops[-1:],
                       [p[1] for p in pairs]), **kw) is None


# -- correct, where a router can tip ---------------------------------------------


def _samples(cell, seed, n=6, prompt=30, out=80):
    vocab = cell.family.vocab(cell.config)
    rng = np.random.default_rng(seed)

    def toks(k):
        return [int(t) for t in rng.integers(1, vocab, k)]
    return [(toks(prompt + i), toks(out)) for i in range(n)]


@pytest.mark.parametrize("quant", ["int8", "fp8"])
def test_control_is_not_correct(repo, quant):
    cell = cells.Cell(CELL, repo=repo)
    gaps, margins = serve_common.served_gaps(cell, 7, _samples(cell, 7),
                                             control=quant)
    assert len(gaps) == 480 and (gaps >= 0).all()
    ok, compared = correct.judge(serve_common.gap_numbers(gaps, margins),
                                 cell.settings["limits"])
    assert not ok, compared


def test_altered_token_is_not_correct(repo):
    cell = cells.Cell(CELL, repo=repo)
    vocab = cell.family.vocab(cell.config)

    def fault(loop):
        def alter(tr, s):
            if len(s.out) == 2 and not getattr(s, "_altered", False):
                s._altered = True
                s.out[-1] = s.pending_tok = (s.out[-1] + 1) % vocab
        loop.on_token = alter
    env = pb_tiny.make_env(os.path.join(repo, ".trace"))
    args = pb_tiny.args(seed=7)
    result = cells.kind_module(cell.kind, repo).run(cell, args, env,
                                                    fault=fault)
    line = pbrun.result_line(cell, args, result, env)
    assert line["correct"] is False


def test_a_tipped_last_place_alone_is_not_a_fault(repo):
    """A program whose router differs from the reference's by less than
    a rounding (here: 1e-3 on the correction bias of a held expert, half
    of NEAR_TIE) puts another expert in last place exactly where the
    reference's own margin is under that: those positions are not
    judged, and the run is ``correct``.  With the rule off the same
    tokens are not: the rule is what makes the difference."""
    import jax.numpy as jnp
    from pb import weights
    cell = cells.Cell(CELL, repo=repo)
    cfg, ref = cell.config, cell.reference
    seed = 11
    w = weights.make_weights(cell.family, cfg, seed, "float32")
    tipped = dict(w)
    for name in w:
        if name.endswith("experts.router_bias"):
            tipped[name] = w[name].at[cfg["experts_held"][0]].add(1e-3)
    rng = np.random.default_rng(seed)
    ids = jnp.asarray(rng.integers(1, cell.family.vocab(cfg), (16, 120)),
                      jnp.int32)
    theirs, near = ref.logits(cfg, w, ids)
    mine, _ = ref.logits(cfg, tipped, ids)
    picked = jnp.argmax(mine, -1)
    moved = np.asarray(picked != jnp.argmax(theirs, -1))
    near = np.asarray(near)
    assert near.any() and not near.all()
    # whatever the tip moved at its own position lies among the near ties
    # (a tip also moves later positions, through the cache, by less than
    # a rounding: at most a token whose margin is one too)
    judged = {}
    for tau in (ref.NEAR_TIE, 0.0):
        ref.NEAR_TIE = tau
        ref._gap_fn.cache_clear()
        try:
            g, m = ref.served_token_gaps(cfg, w, ids, picked)
        finally:
            ref.NEAR_TIE = 2e-3
            ref._gap_fn.cache_clear()
        judged[tau] = serve_common.gap_numbers(
            np.asarray(g).ravel(), np.asarray(m).ravel())
    off = judged[0.0]["served_sq_gap_per_close_call"]
    on = judged[2e-3]["served_sq_gap_per_close_call"]
    assert on <= off
    if moved.any():
        assert off > 0
