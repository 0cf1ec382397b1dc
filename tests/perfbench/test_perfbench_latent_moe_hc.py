"""The latent-attention mixture of experts with a residual of several
streams (``perfbench/families/latent_moe_hc.py``, its reference, the
readers of the program's stream counters and of the mHC kernels): counts
against hand counts, each new metric's reader fed a
synthetic trace, the reference's imports, the declarations, and the
control."""
import json
import os
import re

import numpy as np
import pytest

import pb_tiny
from pb import cells, correct, peaks, serve_common

CELL = "xing4-serve-decode-streams"
NAME = "xing4.0-29b-a4b-l5"
FAMILY = cells.family_module("latent_moe_hc")
with open(os.path.join(pb_tiny.BENCH, "configs", NAME + ".json")) as _f:
    CONFIG = json.load(_f)


@pytest.fixture(scope="module")
def repo(tmp_path_factory):
    yield pb_tiny.make_repo(tmp_path_factory.mktemp("pb_hc"))
    from apex_tpu.runtime import step_cache
    step_cache.clear()


# -- counts ------------------------------------------------------------------


def test_parameter_counts_are_the_cuts_arithmetic():
    """The cut's arithmetic: 1 dense + 4 routed layers at published
    widths, every expert and the whole vocabulary, 4.048 G parameters."""
    cfg = CONFIG
    plain = FAMILY._plain
    # MLA: 3584x768 + 768x32x192 + 3584x576 + 512x32x256 + 32x128x3584
    assert plain.attn_params(cfg) == 2752512 + 4718592 + 2064384 \
        + 4194304 + 14680064 == 28409856
    # a layer's two mappings: Phi (24, 14336), alpha (3), bias (24)
    mapping = 2 * (24 * 14336 + 27)
    assert FAMILY.mapping_params(cfg) == 5 * mapping == 5 * 688182
    norms = 2 * 3584 + 768 + 512
    dense = 28409856 + 3 * 3584 * 9216 + norms + mapping
    routed = 28409856 + 65 * 3 * 3584 * 1024 + 64 * 3584 + 64 + norms \
        + mapping
    assert 128.19e6 < dense < 128.2e6 and 744.98e6 < routed < 745.0e6
    want = dense + 4 * routed + 2 * 131072 * 3584 + 3584
    total = sum(int(np.prod(s)) for s in FAMILY.leaf_shapes(cfg).values())
    assert total == want == 4047680782                     # 8.10 GB bf16
    assert list(FAMILY.leaf_shapes(cfg))[:18][-6:] == [
        f"blocks.0.hc_{s}.{p}" for s in ("attn", "ffn")
        for p in ("phi", "alpha", "bias")]


TICK = {"decode_batch": 256, "kv_tokens": 256 * 1024, "dispatches":
        ["decode_step"], "moe_pairs": 4096, "moe_experts_hit": 256,
        "hc_rows": 256, "hc_sublayers": 10}


def test_mapping_counts_against_hand_counts():
    cfg = CONFIG
    # the least bytes are each sublayer's Phi (24 x 14336), alpha (3) and
    # bias (24) once a call, bf16: the streams stay on the chip's core
    phi = 2 * 10 * (24 * 14336 + 27)
    assert FAMILY.hyper_conn_bytes(cfg, TICK) == phi == 6881820
    # the norm, 24 logits, h, the mix and add; 20 iterations of 16 entries
    flops = 256 * 10 * (2 * 14336 * (24 + 3 + 4) + 4 * 16 * 20)
    assert FAMILY.hyper_conn_flops(cfg, TICK) == flops
    # the least time of a tick's kernels: 11.6 us of the MXU's peak
    assert 11.5e-6 < flops / 197e12 < 11.7e-6 and phi / 819e9 < 8.5e-6
    # without the program's counters: the batch and the configuration's own
    bare = {k: v for k, v in TICK.items() if not k.startswith("hc_")}
    assert FAMILY.hyper_conn_flops(cfg, bare) == flops
    assert FAMILY.decode_step_bytes(cfg, TICK) \
        == FAMILY._plain.decode_step_bytes(cfg, TICK) + phi
    assert FAMILY.decode_step_flops(cfg, TICK) \
        == FAMILY._plain.decode_step_flops(cfg, TICK) + flops


# -- the readers, fed a synthetic trace ------------------------------------------


def _tick(i, hc=True, kinds=("decode_step",)):
    tk = {"t0": 1.0 + i, "t1": 1.5 + i, "dispatches": list(kinds),
          "decode_batch": 256, "kv_tokens": 256 * 1024}
    rec = {"span": "serve.step", "id": i, "parent": None,
           "t0_ns": int((1.1 + i) * 1e9), "t1_ns": int((1.4 + i) * 1e9)}
    if hc:
        rec.update(hc_rows=250 + i, hc_sublayers=10)
    return tk, rec


def _ctx(pairs, ops, modules):
    return {"cfg": CONFIG, "family": FAMILY,
            "peaks": peaks.PEAKS["TPU v5 lite"],
            "counters": {"ticks": [p[0] for p in pairs],
                         "dispatch_log": [k for p in pairs
                                          for k in p[0]["dispatches"]],
                         "module_prefixes": ["jit_fn("]},
            "trace": {"ops": ops, "modules": modules},
            "span_records": [p[1] for p in pairs], "span_children": {}}


def _trace():
    """Two decode ticks of 12 ms (each 2 + 1 ms of the kernels'
    operations) and a prefill chunk of 30 ms between (5 ms of them): only
    the decode programs' are read."""
    ms = 1_000_000
    modules = [("jit_fn(a)", 0, 12 * ms), ("jit_fn(a)", 20 * ms, 30 * ms),
               ("jit_fn(a)", 60 * ms, 12 * ms)]
    ops = []
    for t0 in (0, 60 * ms):
        ops += [(f"%hyper_conn_pre.{t0} = f32[8] custom-call()", t0 + ms,
                 2 * ms),
                (f"%hyper_conn_post.{t0} = f32[8] custom-call()",
                 t0 + 4 * ms, ms),
                ("%fusion.7 = f32[8] fusion()", t0 + 6 * ms, 5 * ms)]
    ops.append(("%hyper_conn_pre.9 = f32[8] custom-call()", 25 * ms,
                5 * ms))
    return ops, modules


def _pairs(hc=True):
    return [_tick(0, hc), _tick(1, hc, ("prefill_step",)), _tick(2, hc)]


def test_step_share_reads_the_decode_programs_alone():
    ops, modules = _trace()
    reader, kw = cells.metric_reader("hyper_conn_step_share")
    assert reader(_ctx(_pairs(), ops, modules), **kw) == \
        pytest.approx(100 * 6 / 24, rel=1e-9)
    # no kernel's operation in the trace (a parent): nothing to read
    plain = [o for o in ops if "fusion" in o[0]]
    assert reader(_ctx(_pairs(), plain, modules), **kw) is None
    # the dispatch log and the trace disagree: nothing is attributed
    assert reader(_ctx(_pairs(), ops, modules[:2]), **kw) is None


def test_roofline_is_the_ticks_least_time_over_the_kernels_time():
    ops, modules = _trace()
    reader, kw = cells.metric_reader("hyper_conn_roofline")
    ticks = [dict(p[0], hc_rows=p[1]["hc_rows"], hc_sublayers=10)
             for p in _pairs() if "decode_step" in p[0]["dispatches"]]
    # compute-bound: the operations over the peak beat Phi over 819 GB/s
    least = sum(max(FAMILY.hyper_conn_flops(CONFIG, tk) / 197e12,
                    FAMILY.hyper_conn_bytes(CONFIG, tk) / 819e9)
                for tk in ticks)
    got = reader(_ctx(_pairs(), ops, modules), **kw)
    assert got == pytest.approx(100 * least / 0.006, rel=1e-9)
    assert 0 < got < 100
    # a program that keeps no stream counters: nothing to read, no error
    assert reader(_ctx(_pairs(hc=False), ops, modules), **kw) is None
    assert reader(dict(_ctx(_pairs(), ops, modules), trace=None),
                  **kw) is None


# -- the reference and the declarations --------------------------------------------


def test_the_reference_imports_nothing_of_the_program():
    path = os.path.join(pb_tiny.REPO, CONFIG["reference"])
    with open(path) as f:
        code = f.read()
    assert not re.search(r"apex_tpu|from \.\.|import sut", code)
    imports = re.findall(r"^(?:from|import) (\S+)", code, re.M)
    assert sorted(set(imports)) == ["__future__", "functools", "jax",
                                    "jax.numpy", "json", "math",
                                    "pb.refmath"]
    # the streams, the mapping and its Sinkhorn loop are written out here
    assert "hc_sinkhorn_iters" in code and "def _mapping" in code


@pytest.mark.parametrize("checkout", pb_tiny.CHECKOUTS)
def test_the_cell_and_its_metrics_are_declared(checkout, tmp_path):
    repo = pb_tiny.checkout(checkout, tmp_path)
    bench = cells.load_benchmark(repo)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name, better, layer in [
            ("hyper_conn_roofline", "higher", "kernel tier"),
            ("hyper_conn_step_share", "lower", "step programs")]:
        m = by_name[name]
        assert (m["unit"], m["better"], m["source"], m["layer"],
                m["moves"]) == ("%", better, "device_trace", layer,
                                "serve_tokens_per_s")
        assert CELL in m["workloads"]
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["chips"]) == (NAME, 1)
    conf = next(c for c in bench["configs"] if c["name"] == NAME)
    assert conf["reduced"] == ["num_hidden_layers", "first_k_dense_replace",
                               "num_nextn_predict_layers",
                               "max_position_embeddings"]
    reports = {m["name"] for m in cells.Cell(CELL, repo=repo).per_layer}
    assert {"routed_experts_roofline", "moe_pairs_per_step",
            "moe_load_max_over_mean", "decode_step_roofline",
            "decode_step_mfu", "decode_device_idle_share",
            "decode_peak_hbm_gb", "decode_batch_mean",
            "decode_compiles_in_window", "decode_step_device_ms",
            "latent_attn_decode_roofline", "hyper_conn_roofline",
            "hyper_conn_step_share"} == reports


def test_every_published_number_is_kept_but_the_cut():
    """The configuration file holds the catalog's config: each number
    under its key, as published, but the keys ``reduced`` lists."""
    published = dict(
        first_k_dense_replace=2, hidden_size=3584, intermediate_size=9216,
        kv_lora_rank=512, max_position_embeddings=262144,
        moe_intermediate_size=1024, moe_layer_freq=1, n_group=1,
        n_routed_experts=64, n_shared_experts=1, num_attention_heads=32,
        num_experts_per_tok=4, num_hidden_layers=40, num_key_value_heads=32,
        num_nextn_predict_layers=1, hc_mult=4, hc_sinkhorn_iters=20,
        hc_eps=1e-06, mhc_h_res_clamp_min=-30, mhc_h_res_clamp_max=30,
        q_lora_rank=768, qk_nope_head_dim=128, qk_rope_head_dim=64,
        rms_norm_eps=1e-06, rope_theta=10000, routed_scaling_factor=2,
        topk_group=1, v_head_dim=128, vocab_size=131072, ep_size=1)
    conf = next(c for c in cells.load_benchmark()["configs"]
                if c["name"] == NAME)
    for key, value in published.items():
        assert (CONFIG[key] == value) != (key in conf["reduced"]), key
    assert CONFIG["experts_held"] == list(range(64))
    assert CONFIG["router_experts"] == 64


# -- correct ----------------------------------------------------------------------


def _samples(cell, seed, n=6, prompt=30, out=80):
    vocab = cell.family.vocab(cell.config)
    rng = np.random.default_rng(seed)

    def toks(k):
        return [int(t) for t in rng.integers(1, vocab, k)]
    return [(toks(prompt + i), toks(out)) for i in range(n)]


def test_control_is_not_correct(repo):
    cell = cells.Cell(CELL, repo=repo)
    gaps, margins = serve_common.served_gaps(cell, 7, _samples(cell, 7),
                                             control="int8")
    assert len(gaps) == 480 and (gaps >= 0).all()
    ok, compared = correct.judge(serve_common.gap_numbers(gaps, margins),
                                 cell.settings["limits"])
    assert not ok, compared
