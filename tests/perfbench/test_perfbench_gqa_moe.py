"""The grouped-query mixture-of-experts family of the benchmark whose
layers mix window and full attention (``perfbench/families/gqa_moe.py``,
its reference, the readers of the program's cache-row counters): counts
against hand counts, each new metric's reader fed a synthetic tick, the
controls that have to come out as not correct, and the near-tie rule of
the reference."""
import json
import os

import numpy as np
import pytest

import pb_tiny
from pb import cells, correct, peaks, serve_common

import run as pbrun

CELL = "mellum2-serve-decode-mixed"
FAMILY = cells.family_module("gqa_moe")
with open(os.path.join(pb_tiny.BENCH, "configs",
                       "mellum2-12b-a2.5b-l8.json")) as _f:
    CONFIG = json.load(_f)


@pytest.fixture(scope="module")
def repo(tmp_path_factory):
    yield pb_tiny.make_repo(tmp_path_factory.mktemp("pb_gqa"))
    from apex_tpu.runtime import step_cache
    step_cache.clear()


# -- the configuration ----------------------------------------------------------


def test_the_configuration_is_the_catalogs_but_for_what_reduced_lists():
    """Every published key as the catalog's row gives it, but the depth
    and the positions reached; the layers' kinds are the published
    pattern's first two periods."""
    bench = cells.load_benchmark()
    conf = next(c for c in bench["configs"]
                if c["name"] == "mellum2-12b-a2.5b-l8")
    assert sorted(conf["reduced"]) == ["max_position_embeddings",
                                       "num_hidden_layers"]
    published = dict(
        attention_bias=False, head_dim=128, hidden_act="silu",
        hidden_size=2304, intermediate_size=7168, max_window_layers=0,
        model_type="mellum", moe_intermediate_size=896, norm_topk_prob=True,
        num_attention_heads=32, num_experts=64, num_experts_per_tok=8,
        num_key_value_heads=4, rms_norm_eps=1e-06, sliding_window=1024,
        tie_word_embeddings=False, vocab_size=98304, use_sliding_window=True)
    for key, value in published.items():
        assert CONFIG[key] == value, key
    assert CONFIG["layer_types"] == (["sliding_attention"] * 3
                                     + ["full_attention"]) * 7
    assert CONFIG["mlp_layer_types"] == ["sparse"] * 28
    full = CONFIG["rope_parameters"]["full_attention"]
    assert (full["rope_type"], full["factor"], full["rope_theta"],
            full["original_max_position_embeddings"], full["beta_fast"],
            full["beta_slow"]) == ("yarn", 16, 500000, 8192, 32, 1)
    assert full["attention_factor"] == pytest.approx(
        0.1 * np.log(16) + 1, rel=1e-12)
    assert CONFIG["rope_parameters"]["sliding_attention"] == {
        "rope_type": "default", "rope_theta": 500000}
    assert FAMILY.layer_windows(CONFIG) == [1024, 1024, 1024, None] * 2
    for key in ("deployment", "assumed", "changed_from_source"):
        assert CONFIG[key]
    assert CONFIG["serve"]["memory_reckoning"]["verdict"]
    mix = cells.Cell(CELL).traffic
    assert (mix["kind"], mix["clients_per_slot"], mix["max_total"],
            mix["cycle"]) == ("closed", 2, 6144, 64)
    assert (mix["prompt"]["lo"], mix["prompt"]["hi"], mix["output"]["lo"],
            mix["output"]["hi"]) == (256, 2048, 1024, 4096)
    assert CONFIG["serve"]["num_blocks"] == \
        CONFIG["serve"]["max_batch"] * mix["max_total"] // 16 + 1


# -- counts ------------------------------------------------------------------


def test_parameter_counts_are_the_issues():
    """ISSUE 33's own arithmetic at the published widths."""
    cfg = CONFIG
    assert FAMILY.attn_params(cfg) == \
        2304 * 4096 + 2 * 2304 * 512 + 4096 * 2304 == 21233664
    assert FAMILY.expert_params(cfg) == 3 * 2304 * 896 == 6193152
    layer = 21233664 + 64 * 2304 + 64 * 6193152 + 2 * 2304
    assert 417.7e6 < layer < 417.9e6                     # 0.836 GB
    want = 8 * layer + 2 * 98304 * 2304 + 2304
    assert FAMILY.total_params(cfg) == want
    assert 3.794e9 < want < 3.796e9                      # 3.795 G, 7.59 GB
    assert FAMILY.kv_row_bytes(cfg) == 2 * 4 * 128 * 2 == 2048
    assert FAMILY.kv_bytes_per_token(cfg) == 16384


TICK = {"decode_batch": 128, "kv_tokens": 331_520, "dispatches":
        ["decode_step"], "moe_pairs": 8200, "moe_experts_hit": 509,
        "kv_rows_full": 331_648, "kv_rows_window": 129_500,
        "kv_layers_full": 2, "kv_layers_window": 6, "kv_window": 1024}
ROWS = 2 * 331_648 + 6 * 129_500


@pytest.mark.parametrize("count,want", [
    # one query a session over the rows its layer reads: a score and a
    # value's share of 128 a head, 32 heads
    ("mixed_attn_decode_flops", ROWS * 4 * 32 * 128),
    # rows once at 2 x 512 x 2 B; a session's 32 queries of 128 bf16 in
    # and 32 outputs of 128 float32 out, a layer
    ("mixed_attn_decode_bytes", ROWS * 2048 + 8 * 128 * 4096 * (2 + 4)),
    ("routed_experts_flops", 8200 * 2 * 6193152),
    # the 509 experts hit once; a pair: 2304 in, 1792 out, 896 in, 2304 out
    ("routed_experts_bytes",
     2 * (509 * 6193152 + 8200 * (2 * 2304 + 3 * 896))),
])
def test_kernel_counts_against_hand_counts(count, want):
    assert getattr(FAMILY, count)(CONFIG, TICK) == want


def test_step_counts_against_hand_counts():
    cfg = CONFIG
    dense = 8 * (21233664 + 64 * 2304) + 98304 * 2304
    assert FAMILY.dense_params(cfg) == dense
    assert FAMILY.decode_step_flops(cfg, TICK) == \
        2 * dense * 128 + 2 * 6193152 * 8200 + ROWS * 4 * 32 * 128
    # every parameter but the embedding's rows (128 are read) and the 3
    # experts of 512 that no token went to; rows read and written
    weights = FAMILY.total_params(cfg) - 98304 * 2304 - 3 * 6193152
    assert FAMILY.decode_step_bytes(cfg, TICK) == \
        2 * (weights + 128 * 2304) + 2048 * (ROWS + 8 * 128)
    # without the program's counters: the router's 8 a token in every
    # layer, every expert, the full layers the depths and the window
    # layers no more than a window a session
    bare = {k: v for k, v in TICK.items()
            if not k.startswith(("moe_", "kv_rows", "kv_layers", "kv_win"))}
    assert FAMILY.routed_experts_flops(cfg, bare) == \
        2 * 6193152 * 128 * 8 * 8
    assert FAMILY.layer_rows(cfg, bare) == 2 * 331_520 + 6 * 128 * 1024
    assert FAMILY.layer_rows(cfg, dict(bare, kv_tokens=5000)) == 8 * 5000
    # least time of a step at the cell's load: the issue's ~12.3 ms
    t = FAMILY.decode_step_bytes(cfg, TICK) / 819e9
    assert 0.0115 < t < 0.0130


# -- the readers, fed a synthetic tick ---------------------------------------------


def _ctx(ticks, ops, records):
    return {"cfg": CONFIG, "family": FAMILY,
            "peaks": peaks.PEAKS["TPU v5 lite"],
            "counters": {"ticks": ticks},
            "trace": {"ops": ops}, "span_records": records,
            "span_children": {}}


def _tick(i, counted=None, kinds=("decode_step",)):
    tk = {"t0": 1.0 + i, "t1": 1.5 + i, "dispatches": list(kinds),
          "decode_batch": 128, "kv_tokens": 331_520}
    rec = {"span": "serve.step", "id": i, "parent": None,
           "t0_ns": int((1.1 + i) * 1e9), "t1_ns": int((1.4 + i) * 1e9)}
    rec.update(counted or {})
    return tk, rec


COUNTED = dict({k: v for k, v in TICK.items()
                if k.startswith(("moe_", "kv_")) and k != "kv_tokens"},
               moe_pairs_max=40, moe_layers=8, moe_held=64)


def test_window_rows_read_share_on_synthetic_records():
    pairs = [_tick(0, COUNTED),
             _tick(1, dict(COUNTED, kv_rows_window=100_000)),
             _tick(2, COUNTED, kinds=("prefill_step",))]
    ctx = _ctx([p[0] for p in pairs], [], [p[1] for p in pairs])
    reader, kw = cells.metric_reader("window_rows_read_share")
    assert reader(ctx, **kw) == pytest.approx(
        (129_500 / 331_648 + 100_000 / 331_648) / 2, rel=1e-12)
    # a program that keeps no such counters: nothing to read, no error
    bare = [_tick(i) for i in range(2)]
    assert reader(_ctx([p[0] for p in bare], [], [p[1] for p in bare]),
                  **kw) is None
    assert reader(_ctx([p[0] for p in bare], [], []), **kw) is None


@pytest.mark.parametrize("metric,op,count_bytes", [
    ("mixed_attn_decode_roofline", "paged_attention_decode",
     "mixed_attn_decode_bytes"),
    ("routed_experts_roofline", "routed_experts", "routed_experts_bytes"),
])
def test_kernel_rooflines_on_a_synthetic_trace(metric, op, count_bytes):
    """Two decode ticks; the trace shows the kernel's operations for 20
    ms in all, and another operation that is none of its business.  Both
    kernels are memory-bound by their counts, so the share is the bytes
    over 819 GB/s over those 20 ms."""
    pairs = [_tick(0, COUNTED), _tick(1, COUNTED)]
    ops = [(f"%{op}.{i} = bf16[8] custom-call()", 0, 5_000_000)
           for i in range(4)] + [("%fusion.7 = f32[8] fusion()", 0, 9e9)]
    ctx = _ctx([p[0] for p in pairs], ops, [p[1] for p in pairs])
    reader, kw = cells.metric_reader(metric)
    least = 2 * getattr(FAMILY, count_bytes)(CONFIG, TICK) / 819e9
    assert reader(ctx, **kw) == pytest.approx(100 * least / 0.020, rel=1e-9)
    assert 0 < reader(ctx, **kw) < 100
    assert reader(_ctx([p[0] for p in pairs], ops[-1:],
                       [p[1] for p in pairs]), **kw) is None
    # the parent's records carry no such counters: nothing, no error
    bare = [_tick(i) for i in range(2)]
    assert reader(_ctx([p[0] for p in bare], ops, [p[1] for p in bare]),
                  **kw) is None


def test_the_new_metrics_are_declared_with_the_cell():
    bench = cells.load_benchmark()
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name, unit, better, source, layer in [
            ("mixed_attn_decode_roofline", "%", "higher", "device_trace",
             "kernel tier"),
            ("window_rows_read_share", "ratio", "lower", "program_counter",
             "step programs")]:
        m = by_name[name]
        assert (m["unit"], m["better"], m["source"], m["layer"],
                m["moves"], m["workloads"]) == (
            unit, better, source, layer, "serve_tokens_per_s", [CELL])
    reports = {m["name"] for m in cells.Cell(CELL).per_layer}
    assert {"routed_experts_roofline", "moe_pairs_per_step",
            "moe_load_max_over_mean", "decode_step_roofline",
            "decode_step_mfu", "decode_device_idle_share",
            "decode_peak_hbm_gb", "decode_batch_mean",
            "decode_compiles_in_window", "decode_step_device_ms",
            "mixed_attn_decode_roofline", "window_rows_read_share"} == reports
    assert len(bench["workloads"]) == 4


# -- correct ----------------------------------------------------------------------


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


def _run(repo, fault):
    cell = cells.Cell(CELL, repo=repo)
    env = pb_tiny.make_env(os.path.join(repo, ".trace"))
    args = pb_tiny.args(seed=7)
    result = cells.kind_module(cell.kind, repo).run(cell, args, env,
                                                    fault=fault)
    return pbrun.result_line(cell, args, result, env)


def test_altered_token_is_not_correct(repo):
    vocab = FAMILY.tiny(CONFIG)["vocab_size"]

    def fault(loop):
        def alter(tr, s):
            if len(s.out) == 2 and not getattr(s, "_altered", False):
                s._altered = True
                s.out[-1] = s.pending_tok = (s.out[-1] + 1) % vocab
        loop.on_token = alter
    assert _run(repo, fault)["correct"] is False


@pytest.mark.parametrize("which", ["band_too_narrow", "window_on_full"])
def test_a_wrong_band_is_not_correct(repo, which):
    """A program whose window layers read fewer keys than the window (at
    this size the band is a large share of what a layer reads, so half a
    band left out moves tokens), and one whose full layers are given the
    window: both serve other tokens than the reference's."""
    def reads(blk, window):
        # the layer's two readers handed another window than its own
        for name in ("read_decode", "read_chunk"):
            read = getattr(blk, name)
            setattr(blk, name, lambda q, pool, layer, tables, pos, _w,
                    read=read: read(q, pool, layer, tables, pos, window))

    def fault(loop):
        for blk in loop.eng.model.blocks:
            if which == "band_too_narrow" and blk.window is not None:
                reads(blk, blk.window // 2)
            elif which == "window_on_full" and blk.window is None:
                reads(blk, 16)
    line = _run(repo, fault)
    assert line["correct"] is False, line["compared"]


def test_a_near_tie_is_not_judged():
    """The rule: a position where, in some layer, the reference's own
    last expert in leads its first one out by less than ``NEAR_TIE`` in
    probability comes back with gap 0 and an infinite margin; with the
    rule off (0) every position is judged."""
    import jax.numpy as jnp
    from pb import weights
    cfg = pb_tiny.tiny_config(CONFIG, pb_tiny.REPO)
    ref = cells.Cell(CELL).reference
    w = weights.make_weights(FAMILY, cfg, 11, "float32")
    rng = np.random.default_rng(11)
    ids = jnp.asarray(rng.integers(1, FAMILY.vocab(cfg), (8, 96)), jnp.int32)
    lg, tie = ref.logits(cfg, w, ids)
    picked = jnp.argmax(lg, -1)
    g, m, t = (np.asarray(x) for x in
               ref.gaps_margins_ties(cfg, w, ids, picked))
    np.testing.assert_allclose(t, np.asarray(tie), rtol=1e-6)
    assert (g == 0).all() and (t > 0).all()       # its own choices
    # a threshold at the median lead leaves half the positions out
    tau = float(np.median(t))
    was, ref.NEAR_TIE = ref.NEAR_TIE, tau
    try:
        _, margins = ref.served_token_gaps(cfg, w, ids, picked)
    finally:
        ref.NEAR_TIE = was
    out = np.isinf(np.asarray(margins))
    assert (out == (t < tau)).all() and 0.4 < out.mean() < 0.6
