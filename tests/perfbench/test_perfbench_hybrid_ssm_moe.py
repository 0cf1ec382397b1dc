"""The state-space hybrid family of the benchmark
(``perfbench/families/hybrid_ssm_moe.py``, its reference, the reader of
the program's state-space counters): the configuration against the
catalog's row, counts against hand counts, the new metric's reader fed a
synthetic tick, the cell ``correct`` plain and traced at ``tiny()``, and
the controls and planted faults that have to come out as not correct."""
import json
import os
import re

import numpy as np
import pytest

import pb_tiny
from pb import cells, correct, peaks, serve_common

import run as pbrun

CELL = "nemotron3-serve-decode-state"
NAME = "nemotron3-nano-30b-a3b-ep4-l13"
FAMILY = cells.family_module("hybrid_ssm_moe")
with open(os.path.join(pb_tiny.BENCH, "configs", NAME + ".json")) as _f:
    CONFIG = json.load(_f)
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def repo(tmp_path_factory):
    yield pb_tiny.make_repo(tmp_path_factory.mktemp("pb_hybrid"))
    from apex_tpu.runtime import step_cache
    step_cache.clear()


# -- the configuration ----------------------------------------------------------


PUBLISHED = dict(
    attention_bias=False, chunk_size=128, conv_kernel=4, expand=2,
    head_dim=128, hidden_size=2688, intermediate_size=1856,
    layer_norm_epsilon=1e-05, mamba_head_dim=64, mamba_hidden_act="silu",
    mamba_num_heads=64, mamba_proj_bias=False, mlp_bias=False,
    mlp_hidden_act="relu2", model_type="nemotron_h",
    moe_intermediate_size=1856, moe_shared_expert_intermediate_size=3712,
    n_group=1, n_groups=8, n_shared_experts=1, norm_eps=1e-05,
    norm_topk_prob=True, num_attention_heads=32, num_experts_per_tok=6,
    num_key_value_heads=2, num_logits_to_keep=1, partial_rotary_factor=1,
    rescale_prenorm_residual=True, residual_in_fp32=False, rope_theta=10000,
    routed_scaling_factor=2.5, sliding_window=None, ssm_state_size=128,
    tie_word_embeddings=False, time_step_floor=0.0001, time_step_max=0.1,
    time_step_min=0.001, topk_group=1, use_bias=False, use_conv_bias=True,
    use_mamba_kernels=True)
REDUCED = dict(num_hidden_layers=13, hybrid_override_pattern="MEMEM*EMEMEM*",
               n_routed_experts=32, vocab_size=32768,
               max_position_embeddings=4096)


def test_the_configuration_is_the_catalogs_but_for_what_reduced_lists():
    """Every published key as the catalog's row gives it, but the depth
    with its pattern, the experts held, the vocabulary and the positions
    reached; no width, head count, state size, group count, convolution
    width, chunk size or experts a token among them."""
    conf = next(c for c in cells.load_benchmark()["configs"]
                if c["name"] == NAME)
    assert sorted(conf["reduced"]) == sorted(REDUCED)
    for key, value in {**PUBLISHED, **REDUCED}.items():
        assert CONFIG[key] == value, key
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["source_url"] == conf["source"])
        assert conf["source"] == CONFIG["source"] == row["source_url"]
        for key, value in row["config"].items():
            assert key in REDUCED or CONFIG[key] == value, key
        assert row["config"]["hybrid_override_pattern"].startswith(
            CONFIG["hybrid_override_pattern"])
    assert CONFIG["router_experts"] == 128
    assert CONFIG["experts_held"] == list(range(32))
    assert FAMILY.pattern(CONFIG) == "MEMEM*EMEMEM*"
    assert [FAMILY.layers_of(CONFIG, k) for k in "ME*"] == [6, 5, 2]
    for key in ("deployment", "assumed", "changed_from_source"):
        assert CONFIG[key]
    assert set(CONFIG["changed_from_source"]) == set(REDUCED) | {"note"}
    assert CONFIG["deployment"]["expert_parallel"] == 4
    assert "12 pairs" in CONFIG["deployment"]["expert_load"] \
        and "48" in CONFIG["deployment"]["expert_load"]
    assert CONFIG["serve"]["memory_reckoning"]["verdict"]
    for key in FAMILY.READS:
        assert key in CONFIG, key
    cell = cells.Cell(CELL)
    mix, sv = cell.traffic, CONFIG["serve"]
    assert (cell.traffic_name, cell.chips) == (
        "reasoning-long-out-closed-state", 1)
    assert (mix["kind"], mix["clients_per_slot"], mix["max_total"],
            mix["cycle"]) == ("closed_state", 2, 4096, 64)
    # the mix ISSUE 35 names, parameter for parameter: only the kind
    # differs, which runs kind ``closed`` and compares the state besides
    with open(os.path.join(pb_tiny.BENCH, "traffic",
                           "reasoning-long-out-closed.json")) as f:
        named = json.load(f)
    notes = {"kind", "why", "warm_why"}
    assert named["kind"] == "closed"
    assert {k: v for k, v in mix.items() if k not in notes} == \
        {k: v for k, v in named.items() if k not in notes}
    assert (mix["prompt"]["lo"], mix["prompt"]["hi"], mix["output"]["lo"],
            mix["output"]["hi"]) == (256, 1024, 1024, 3072)
    assert (sv["max_batch"], sv["block_size"], sv["prefill_chunk"],
            sv["draft"], sv["prefix_cache"]) == (256, 16, 512, None, False)
    assert sv["num_blocks"] == sv["max_batch"] * mix["max_total"] // 16 + 1


# -- counts ------------------------------------------------------------------


def test_parameter_counts_are_the_issues():
    """ISSUE 35's own arithmetic at the published widths."""
    cfg = CONFIG
    assert FAMILY.mamba_inner(cfg) == 64 * 64 == 4096
    assert FAMILY.conv_dim(cfg) == 4096 + 2 * 8 * 128 == 6144
    assert FAMILY.mamba_params(cfg) == 2688 * 10304 + 4096 * 2688 == 38707200
    assert FAMILY.attn_params(cfg) == \
        2 * 2688 * 4096 + 2 * 2688 * 256 == 23396352
    assert FAMILY.expert_params(cfg) == 2 * 2688 * 1856 == 9977856
    assert FAMILY.shared_params(cfg) == 2 * 2688 * 3712 == 19955712
    mamba = 38707200 + 6144 * 5 + 3 * 64 + 4096 + 2688      # 38.75 M
    attn = 23396352 + 2688
    experts = 32 * 9977856 + 19955712 + 128 * 2688 + 128 + 2688   # 339.6 M
    want = 6 * mamba + 2 * attn + 5 * experts + 2 * 32768 * 2688 + 2688
    assert FAMILY.total_params(cfg) == want == 2153400832   # 4.31 GB
    assert 339.5e6 < experts < 339.7e6 and 38.7e6 < mamba < 38.8e6
    assert FAMILY.kv_row_bytes(cfg) == 2 * 2 * 128 * 2 == 1024
    assert FAMILY.kv_bytes_per_token(cfg) == 2048
    assert FAMILY.state_bytes_per_session(cfg) == \
        128 * 4096 * 4 + 3 * 6144 * 2 == 2097152 + 36864
    shapes = FAMILY.leaf_shapes(cfg)
    assert shapes["blocks.1.experts.w_in"] == (32, 1856, 2688) == \
        shapes["blocks.1.experts.w_out"]
    assert shapes["blocks.0.mixer.in_proj"] == (2688, 10304)


TICK = {"decode_batch": 256, "kv_tokens": 424_960, "dispatches":
        ["decode_step"], "moe_pairs": 1900, "moe_experts_hit": 158,
        "ssm_sessions": 255, "ssm_layers": 6,
        "ssm_state_bytes": 2 * 255 * 2134016}


@pytest.mark.parametrize("count,want", [
    # five operations an element of a (128, 4096) state, 255 sessions in
    # 6 layers
    ("ssm_state_update_flops", 5 * 128 * 4096 * 255 * 6),
    # H of each live session once in and once out, a layer: the
    # convolution's kept inputs (36,864 B) are no operand of the step
    ("ssm_state_update_bytes", 6 * 2 * 255 * 2097152),
    # a query of 32 heads of 128 over every cached token of 2 layers
    ("paged_attn_decode_flops", 424_960 * 2 * 4 * 32 * 128),
    # a K and a V row of 2 stored heads a token a layer; a session's
    # query in and output out, 32 heads of 128, a layer
    ("paged_attn_decode_bytes",
     424_960 * 2 * 1024 + 256 * 2 * 2 * 32 * 128 * 2),
    ("routed_experts_flops", 1900 * 2 * 9977856),
    # the 158 experts hit once; a pair: 2688 in, 1856 out, 1856 in, 2688 out
    ("routed_experts_bytes",
     2 * (158 * 9977856 + 1900 * (2 * 2688 + 2 * 1856))),
])
def test_kernel_counts_against_hand_counts(count, want):
    assert getattr(FAMILY, count)(CONFIG, TICK) == want


def test_step_counts_against_hand_counts():
    cfg = CONFIG
    dense = 6 * 38707200 + 2 * 23396352 \
        + 5 * (128 * 2688 + 19955712) + 32768 * 2688
    assert FAMILY.dense_params(cfg) == dense
    assert FAMILY.decode_step_flops(cfg, TICK) == \
        2 * dense * 256 + 2 * 9977856 * 1900 + 424_960 * 2 * 4 * 32 * 128 \
        + 5 * 128 * 4096 * 255 * 6
    # every parameter but the embedding's rows (256 are read) and the 2
    # held experts of 160 that no token went to; rows read and written;
    # the state both ways
    weights = FAMILY.total_params(cfg) - 32768 * 2688 - 2 * 9977856
    assert FAMILY.decode_step_bytes(cfg, TICK) == \
        2 * (weights + 256 * 2688) + 2048 * (424_960 + 256) \
        + 6 * 2 * 255 * 2134016
    # without the program's counters: the router's 6 a token of which a
    # quarter come here, every held expert, the batch's sessions
    bare = {k: v for k, v in TICK.items()
            if not k.startswith(("moe_", "ssm_"))}
    assert FAMILY.routed_experts_flops(cfg, bare) == \
        2 * 9977856 * 256 * 5 * 6 * 32 / 128
    assert FAMILY.ssm_state_update_bytes(cfg, bare) == 6 * 2 * 256 * 2097152
    # the issue's reckoning of a step at the cell's load: 6.5 GB of state
    # (6.44 of it the recurrence's own operand), ~11.5 GB in all, ~14 ms
    assert 6.5e9 < 6 * 2 * 256 * FAMILY.state_bytes_per_session(cfg) < 6.6e9
    assert 6.4e9 < FAMILY.ssm_state_update_bytes(cfg, bare) < 6.5e9
    t = FAMILY.decode_step_bytes(cfg, bare) / 819e9
    assert 0.0138 < t < 0.0144


# -- the reader, fed a synthetic tick ---------------------------------------------


def _ctx(ticks, ops, records):
    return {"cfg": CONFIG, "family": FAMILY,
            "peaks": peaks.PEAKS["TPU v5 lite"],
            "counters": {"ticks": ticks},
            "trace": {"ops": ops}, "span_records": records,
            "span_children": {}}


def _tick(i, counted=None, kinds=("decode_step",)):
    tk = {"t0": 1.0 + i, "t1": 1.5 + i, "dispatches": list(kinds),
          "decode_batch": 256, "kv_tokens": 424_960}
    rec = {"span": "serve.step", "id": i, "parent": None,
           "t0_ns": int((1.1 + i) * 1e9), "t1_ns": int((1.4 + i) * 1e9)}
    rec.update(counted or {})
    return tk, rec


COUNTED = dict({k: v for k, v in TICK.items() if k.startswith(("moe_", "ssm_"))},
               moe_pairs_max=40, moe_layers=5, moe_held=32)


@pytest.mark.parametrize("metric,op,count_bytes", [
    ("ssm_state_update_roofline", "ssm_state_update",
     "ssm_state_update_bytes"),
    ("routed_experts_roofline", "routed_experts", "routed_experts_bytes"),
])
def test_kernel_rooflines_on_a_synthetic_trace(metric, op, count_bytes):
    """Two decode ticks; the trace shows the kernel's operations for 40
    ms in all, and another operation that is none of its business.  Both
    kernels are memory-bound by their counts, so the share is the bytes
    over 819 GB/s over those 40 ms."""
    pairs = [_tick(0, COUNTED), _tick(1, COUNTED),
             _tick(2, COUNTED, kinds=("prefill_step",))]
    ops = [(f"%{op}.{i} = f32[8] custom-call()", 0, 10_000_000)
           for i in range(4)] + [("%fusion.7 = f32[8] fusion()", 0, 9e9)]
    ctx = _ctx([p[0] for p in pairs], ops, [p[1] for p in pairs])
    reader, kw = cells.metric_reader(metric)
    least = 2 * getattr(FAMILY, count_bytes)(CONFIG, TICK) / 819e9
    assert reader(ctx, **kw) == pytest.approx(100 * least / 0.040, rel=1e-9)
    assert 0 < reader(ctx, **kw) < 100
    # no such operation in the trace: nothing to read
    assert reader(_ctx([p[0] for p in pairs], ops[-1:],
                       [p[1] for p in pairs]), **kw) is None
    # the parent's records carry no such counters: nothing, no error
    bare = [_tick(i) for i in range(2)]
    assert reader(_ctx([p[0] for p in bare], ops, [p[1] for p in bare]),
                  **kw) is None
    assert reader(_ctx([p[0] for p in bare], ops, []), **kw) is None


def test_the_attention_readers_share_on_a_synthetic_trace():
    """``paged_attn_decode_roofline`` needs no counter of the program:
    the ticks' own depths and the family's counts."""
    pairs = [_tick(0), _tick(1), _tick(2, kinds=("prefill_step",))]
    ops = [(f"%paged_attention_decode.{i} = bf16[8] custom-call()", 0,
            5_000_000) for i in range(4)]
    ctx = _ctx([p[0] for p in pairs], ops, [p[1] for p in pairs])
    reader, kw = cells.metric_reader("paged_attn_decode_roofline")
    least = 2 * FAMILY.paged_attn_decode_bytes(CONFIG, TICK) / 819e9
    assert reader(ctx, **kw) == pytest.approx(100 * least / 0.020, rel=1e-9)
    assert 0 < reader(ctx, **kw) < 100
    assert reader(_ctx([p[0] for p in pairs], [], []), **kw) is None


def test_the_new_metric_is_declared_with_the_cell():
    bench = cells.load_benchmark()
    by_name = {m["name"]: m for m in bench["per_layer"]}
    m = by_name["ssm_state_update_roofline"]
    assert (m["unit"], m["better"], m["source"], m["layer"], m["moves"],
            m["workloads"]) == ("%", "higher", "device_trace", "kernel tier",
                                "serve_tokens_per_s", [CELL])
    assert bench["per_layer"][-1] is m and bench["workloads"][-1]["name"] \
        == CELL and bench["configs"][-1]["name"] == NAME
    reports = {m["name"] for m in cells.Cell(CELL).per_layer}
    assert {"routed_experts_roofline", "moe_pairs_per_step",
            "moe_load_max_over_mean", "decode_step_roofline",
            "decode_step_mfu", "decode_device_idle_share",
            "decode_peak_hbm_gb", "decode_batch_mean",
            "decode_compiles_in_window", "decode_step_device_ms",
            "paged_attn_decode_roofline", "ssm_state_update_roofline"} \
        == reports
    assert by_name["paged_attn_decode_roofline"]["workloads"] == [
        "gpt2m-serve-decode", CELL]
    # what the three older serving cells report is what it was
    older = {"gpt2m-serve-decode": 10, "gigachat3-serve-decode": 11,
             "mellum2-serve-decode-mixed": 12}
    for name, n in older.items():
        assert len(cells.Cell(name).per_layer) == n, name
    assert [w["name"] for w in bench["workloads"]] == [
        "gpt2s-train", *older, CELL]
    assert all(w["chips"] == 1 for w in bench["workloads"])


def test_the_reference_imports_nothing_of_the_program():
    path = os.path.join(pb_tiny.REPO, CONFIG["reference"])
    with open(path) as f:
        code = f.read()
    assert not re.search(r"apex_tpu|from \.\.|import sut", code)
    imports = re.findall(r"^(?:from|import) (\S+)", code, re.M)
    assert sorted(set(imports)) == ["__future__", "functools", "jax",
                                    "jax.numpy", "json", "math",
                                    "pb.refmath"]
    assert "lax.scan(step" in code          # the recurrence, step by step


# -- correct ----------------------------------------------------------------------


def _run(repo, fault=None, trace=0):
    cell = cells.Cell(CELL, repo=repo)
    env = pb_tiny.make_env(os.path.join(repo, ".trace"))
    args = pb_tiny.args(seed=7, trace=trace)
    result = cells.kind_module(cell.kind, repo).run(cell, args, env,
                                                    fault=fault)
    return pbrun.result_line(cell, args, result, env)


@pytest.mark.parametrize("trace", [0, 1], ids=["plain", "traced"])
def test_the_cell_is_correct_at_tiny_sizes(repo, trace):
    """Float32 serving through slots that many sessions reuse (the batch
    is 4) picks the reference's own token everywhere; a traced run
    reports the counters' metrics (the shares of a peak have no chip to
    read here)."""
    line = _run(repo, trace=trace)
    assert line["correct"] is True and line["failed"] == 0, line["compared"]
    assert line["attempted"] > 20
    # the state the sessions left in their slots is the recurrence's
    assert set(line["compared"]) == {"served_sq_gap_per_close_call",
                                     "wrong_length", "state_gap"}
    assert 0 < line["compared"]["state_gap"][0] <= 1e-5
    if trace:
        assert {"decode_batch_mean", "decode_compiles_in_window",
                "moe_pairs_per_step", "moe_load_max_over_mean"} \
            <= set(line["metrics"])
        assert line["metrics"]["decode_compiles_in_window"]["value"] == 0
    else:
        assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}


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


def _altered_token(loop):
    vocab = FAMILY.tiny(CONFIG)["vocab_size"]

    def alter(tr, s):
        if len(s.out) == 2 and not getattr(s, "_altered", False):
            s._altered = True
            s.out[-1] = s.pending_tok = (s.out[-1] + 1) % vocab
    loop.on_token = alter


def test_an_altered_token_is_not_correct(repo):
    """One token a session altered on its way back in: the comparison
    refuses it.  (The faults of a state a session move a logit by a
    thousandth and no token at these sizes: ``state_gap`` refuses a
    state kept in bfloat16 and a slot not started from zero, below;
    ``tests/test_serve_state_moe.py`` holds all four on logits.)"""
    line = _run(repo, _altered_token)
    assert line["correct"] is False, line["compared"]


TOOL = cells._module_from(os.path.join(
    pb_tiny.BENCH, "tools", "readings_state.py"), "tool")


@pytest.mark.parametrize("fault", ["bf16_state", "stale_slot"])
def test_a_fault_of_the_state_is_not_correct(repo, fault):
    """The two faults the served tokens cannot show (a state kept in
    bfloat16, a reused slot not started from zero), planted as the chip's
    readings plant them: ``state_gap`` refuses both."""
    line = _run(repo, lambda loop: TOOL.MODEL_FAULTS[fault](loop.eng.model))
    value, limit = line["compared"]["state_gap"]
    assert line["correct"] is False and value > 100 * limit, line["compared"]
    assert line["compared"]["wrong_length"] == [0.0, 0]


def test_the_control_keeps_its_state_in_bfloat16(repo):
    """The reference one precision down rounds the recurrence's state
    too: its state, in the program's place, is not correct, and the
    reference's own ``n``-th state is what a sequence cut there leaves."""
    import jax.numpy as jnp
    from pb import weights
    cell = cells.Cell(CELL, repo=repo)
    cfg, ref = cell.config, cell.reference
    assert ref.clean_state_layers(cfg) == [0]       # M before the first E
    assert ref.clean_state_layers(CONFIG) == [0]
    w = weights.make_weights(FAMILY, cfg, 5, "float32")
    rng = np.random.default_rng(5)
    ids = rng.integers(1, FAMILY.vocab(cfg), (2, 96)).astype(np.int32)
    lengths = jnp.asarray([96, 41], jnp.int32)
    whole = np.asarray(ref.session_states(cfg, w, jnp.asarray(ids), lengths))
    assert whole.shape == (2, 1, 4, 64, 16)
    cut = ids.copy()
    cut[1, 41:] = 0                 # what follows leaves the state alone
    np.testing.assert_array_equal(np.asarray(ref.session_states(
        cfg, w, jnp.asarray(cut), lengths))[1], whole[1])
    short = np.asarray(ref.session_states(
        cfg, w, jnp.asarray(ids[1:, :41]), jnp.asarray([41], jnp.int32)))
    np.testing.assert_allclose(short[0], whole[1], rtol=1e-6, atol=1e-9)
    kind = cells.kind_module(cell.kind, repo)
    taken = {"sessions": [{"ids": list(ids[i, :n])} for i, n in
                          enumerate((96, 41))], "states": whole}
    assert (kind.state_gaps(cell, 5, taken) == 0).all()
    for quant in ("int8", "fp8"):
        gaps = kind.state_gaps(cell, 5, taken, control=quant)
        assert gaps.shape == (2, 4) and gaps.max(axis=1).min() > 1e-3
        assert not correct.judge({"state_gap": float(gaps.max())},
                                 cell.settings["limits"])[0]


def test_a_near_tie_is_not_judged():
    """The rule: a position where, in some expert layer, the reference's
    own last expert in leads its first one out by less than ``NEAR_TIE``
    in biased score, one of them held here, comes back with gap 0 and an
    infinite margin; with the rule off (0) every position is judged."""
    import jax.numpy as jnp
    from pb import weights
    cfg = pb_tiny.tiny_config(CONFIG, pb_tiny.REPO)
    ref = cells.Cell(CELL).reference
    w = weights.make_weights(FAMILY, cfg, 11, "float32")
    rng = np.random.default_rng(11)
    ids = jnp.asarray(rng.integers(1, FAMILY.vocab(cfg), (4, 96)), jnp.int32)
    lg, tie = ref.logits(cfg, w, ids)
    picked = jnp.argmax(lg, -1)
    g, m, t = (np.asarray(x) for x in
               ref.gaps_margins_ties(cfg, w, ids, picked))
    np.testing.assert_allclose(t, np.asarray(tie), rtol=1e-6)
    assert (g == 0).all() and (t > 0).all()       # its own choices
    assert ref.NEAR_TIE >= 0
    tau = float(np.median(t))
    was, ref.NEAR_TIE = ref.NEAR_TIE, tau
    try:
        _, margins = ref.served_token_gaps(cfg, w, ids, picked)
    finally:
        ref.NEAR_TIE = was
    out = np.isinf(np.asarray(margins))
    assert (out == (t < tau)).all() and 0.4 < out.mean() < 0.6
