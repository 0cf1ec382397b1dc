"""The yardstick's own arithmetic: the traffic generator, the FLOP and
byte counts, the trace reduction, and what the seed gives.  Nothing here
needs a device."""
import hashlib
import json
import os
import re

import numpy as np
import pytest

import pb_tiny  # noqa: F401  (puts perfbench/ on sys.path)
from pb import cells, peaks, trace, traffic
from pb.counts import roofline_seconds

#: the GPT-2 family's counts: the hand counts below were written against
#: ``pb/counts.py``, whose functions moved there unchanged (PR 28)
counts = cells.family_module("gpt")

HERE = os.path.dirname(os.path.abspath(__file__))
BIG = 2 ** 31 + 12345           # the driver's seeds pass 32 signed bits


def _cfg(name):
    with open(os.path.join(pb_tiny.BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def _mix(name):
    with open(os.path.join(pb_tiny.BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


# -- generator ---------------------------------------------------------------


@pytest.mark.parametrize("mix_name", ["short-in-long-out-closed"])
def test_requests_are_deterministic_and_stratified(mix_name):
    mix = _mix(mix_name)
    a = traffic.serve_requests(mix, BIG, 50257, 96)
    b = traffic.serve_requests(mix, BIG, 50257, 96)
    c = traffic.serve_requests(mix, BIG + 1, 50257, 96)
    assert a == b
    assert [r["prompt"] for r in a] != [r["prompt"] for r in c]
    # every seed offers the same multiset of sizes, in another order
    for key in (lambda r: len(r["prompt"]), lambda r: r["max_new"]):
        assert sorted(map(key, a)) == sorted(map(key, c))
        assert list(map(key, a)) != list(map(key, c))
    lo, hi = mix["prompt"]["lo"], mix["prompt"]["hi"]
    lens = sorted(len(r["prompt"]) for r in a)
    assert lo <= lens[0] and lens[-1] <= hi
    assert all(len(r["prompt"]) + r["max_new"] <= mix["max_total"]
               for r in a)
    # successive cycles of a closed loop are other draws of the same sizes
    d = traffic.serve_requests(mix, BIG, 50257, 96, cycle=1)
    assert sorted(r["max_new"] for r in d) == sorted(r["max_new"] for r in a)
    assert [r["prompt"] for r in d] != [r["prompt"] for r in a]


def test_stratified_lengths_are_the_distributions_quantiles():
    dist = {"dist": "uniform", "lo": 100, "hi": 200}
    got = sorted(traffic.stratified_lengths(
        dist, 10, np.random.default_rng(0)))
    assert got == [105, 115, 125, 135, 145, 155, 165, 175, 185, 195]
    with pytest.raises(ValueError):
        traffic.quantile({"dist": "zipf", "lo": 1, "hi": 2}, np.array([0.5]))


def test_every_traffic_key_is_read_by_the_generator_or_its_kind():
    """A key in a mix that nothing reads is a parameter in name only."""
    code = ""
    for folder in ("pb", "kinds"):
        d = os.path.join(pb_tiny.BENCH, folder)
        for f in sorted(os.listdir(d)):
            if f.endswith(".py"):
                code += open(os.path.join(d, f)).read()
    notes = {"why", "warm_why"}
    tdir = os.path.join(pb_tiny.BENCH, "traffic")
    for name in sorted(os.listdir(tdir)):
        mix = _mix(name[:-len(".json")])
        for key in set(mix) - notes:
            assert re.search(r"""["']%s["']""" % re.escape(key), code), \
                (name, key)


def _reads_key(code, key):
    return re.search(r"""["']%s["']""" % re.escape(key), code)


@pytest.mark.parametrize("conf", cells.load_benchmark()["configs"],
                         ids=lambda c: c["name"])
def test_every_size_of_a_configuration_is_read_by_its_family(conf):
    """The sibling for configurations: a family declares the keys it
    reads (``READS``) and reads each; every number at the top of a
    configuration file is one of them, or the reference's, or says the
    same as one (GPT-2's ``n_ctx``); and what is listed as ``reduced``
    is a key that some code reads."""
    cfg = _cfg(os.path.basename(conf["file"])[:-len(".json")])
    family = cells.family_module(cfg["builder"])
    code = open(family.__file__).read()
    for key in family.READS:
        assert _reads_key(code, key), key
    ref_code = open(os.path.join(pb_tiny.REPO, cfg["reference"])).read()
    same_as = {"n_ctx": "n_positions"}
    for key, value in cfg.items():
        if isinstance(value, (int, float)) and not isinstance(value, bool):
            if key in same_as:
                assert value == cfg[same_as[key]]
                continue
            assert key in family.READS or _reads_key(ref_code, key), key
    for key in conf["reduced"]:
        assert key in family.READS, key
    # the serve and train blocks are the harness's: the same keys for
    # every family, read in pb/ and the kinds
    harness = "".join(
        open(os.path.join(pb_tiny.BENCH, folder, f)).read()
        for folder in ("pb", "kinds")
        for f in sorted(os.listdir(os.path.join(pb_tiny.BENCH, folder)))
        if f.endswith(".py"))
    noted = {"note", "kv_bytes_per_token_how", "memory_reckoning",
             "precision", "optimizer", "weight_decay_mode", "loss",
             # pinned against the family's count and the pool's size in
             # test_perfbench_aot_fit.py
             "kv_bytes_per_token", "sessions_of_1024_tokens_in_pool"}
    for block in ("serve", "train"):
        for key in set(cfg.get(block, {})) - noted:
            assert _reads_key(harness, key), (block, key)


# -- what the seed gives: pinned at the parent of PR 28 ----------------------


def _arrays_digest(named):
    h = hashlib.sha256()
    for name, a in named:
        a = np.asarray(a)
        h.update(f"{name}:{a.dtype}:{a.shape};".encode())
        h.update(a.view(f"u{a.dtype.itemsize}").tobytes())
    return h.hexdigest()


with open(os.path.join(HERE, "data", "seeded_digests.json")) as _f:
    PINNED = json.load(_f)


@pytest.mark.parametrize("cell_name", ["gpt2s-train", "gpt2m-serve-decode"])
def test_the_seed_gives_what_it_gave_before_the_families(cell_name):
    """Weights (in the program's order and layout, at the cell's own
    size and in the type they are trained or served in), train batches
    and serve requests of one seed, against digests taken with the
    harness of PR 27 (``tests/perfbench/data/seeded_digests.json``): the
    move into ``families/gpt.py`` changed no bit of them."""
    from pb import sut
    cell = cells.Cell(cell_name)
    cfg, mix, family = cell.config, cell.traffic, cell.family
    seed = PINNED["seed"]
    pinned = PINNED[cell.config_name]
    vals = sut.program_weights(family, cfg, seed, pinned["dtype"])
    assert _arrays_digest((str(i), a) for i, a in enumerate(vals)) == \
        pinned["program_order"]
    del vals
    if cell.kind == "train":
        got = _arrays_digest(
            (str(i), a) for i, a in enumerate(traffic.first_train_batches(
                mix, seed, family.vocab(cfg), 3)))
    else:
        reqs = [traffic.serve_requests(mix, seed, family.vocab(cfg),
                                       mix["cycle"], cycle=c)
                for c in (0, 1)]
        got = hashlib.sha256(json.dumps(reqs).encode()).hexdigest()
    assert got == PINNED[cell.traffic_name]


@pytest.mark.parametrize("name", ["gpt2-small", "gpt2-medium"])
def test_the_benchmarks_own_leaves_are_what_they_were(name):
    """The same for the leaves the reference reads, at the family's tiny
    sizes (the layout at full size is pinned through the program's
    order above)."""
    from pb import weights
    cfg = pb_tiny.tiny_config(_cfg(name), pb_tiny.REPO)
    leaves = weights.make_weights(counts, cfg, PINNED["seed"],
                                  PINNED[name]["dtype"])
    assert set(leaves) == set(counts.leaf_shapes(cfg))
    assert _arrays_digest(sorted(leaves.items())) == \
        PINNED[name]["tiny_leaves"]


def test_train_batches_differ_row_by_row_and_repeat_with_the_seed():
    mix = {"global_batch": 4, "seq_len": 16}
    first = traffic.first_train_batches(mix, BIG, 50257, 3)
    again = traffic.first_train_batches(mix, BIG, 50257, 3)
    assert all(np.array_equal(x, y) for x, y in zip(first, again))
    rows = np.concatenate(first)
    assert len({tuple(r) for r in rows}) == len(rows)
    assert rows.min() >= 0 and rows.max() < 50257


# -- counts ------------------------------------------------------------------


@pytest.mark.parametrize("name,matmul,published", [
    # per layer 4*E*E + 2*E*4E, plus the tied head V*E
    ("gpt2-small", 12 * (4 * 768 ** 2 + 2 * 768 * 3072) + 50257 * 768,
     124_439_808),
    ("gpt2-medium", 24 * (4 * 1024 ** 2 + 2 * 1024 * 4096) + 50257 * 1024,
     354_823_168),
])
def test_parameter_counts_match_hand_counts(name, matmul, published):
    cfg = _cfg(name)
    assert counts.matmul_params(cfg) == matmul
    # with the attention biases the checkpoints carry: the published size
    assert counts.total_params(cfg, attn_bias=True) == published
    e, l = cfg["n_embd"], cfg["n_layer"]
    assert counts.total_params(cfg) == published - l * 4 * e


def test_flops_and_bytes_match_hand_counts():
    small, medium = _cfg("gpt2-small"), _cfg("gpt2-medium")
    # 6 x 123,532,032 + 3 x (4 x 12 x 768 x 512.5)
    assert counts.train_flops_per_token(small, 1024) == pytest.approx(
        6 * 123_532_032 + 3 * 4 * 12 * 768 * 512.5)
    assert counts.kv_bytes_per_token(medium) == 98_304
    # one decode step of 16 sessions 400 deep: weights + KV read + rows
    assert counts.decode_step_min_bytes(medium, 16 * 400, 16) == \
        2 * counts.total_params(medium) + 98_304 * (6400 + 16)
    assert counts.forward_flops(medium, 16, 6400) == pytest.approx(
        2 * 353_453_056 * 16 + 4 * 24 * 1024 * 6400)
    # attention of a 16 x 1024 step: fwd 2 matmuls, bwd 4, causal half
    assert counts.flash_attn_flops_train(small, 16, 1024) == pytest.approx(
        3 * 4 * 12 * 768 * 16 * 1024 * 512.5)
    assert counts.flash_attn_bytes_train(small, 16, 1024) == \
        12 * 12 * 16 * 1024 * 768 * 2
    v5e = peaks.peaks_for("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert roofline_seconds(197e12, 1.0, v5e) == (1.0, "compute")
    assert roofline_seconds(1.0, 819e9, v5e) == (1.0, "memory")
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9")


# -- trace reduction -----------------------------------------------------------


def test_interval_arithmetic():
    merged = trace.union([(0, 10), (5, 20), (30, 40)])
    assert merged == [[0, 20], [30, 40]] and trace.total(merged) == 30
    assert trace.subtract([[0, 20], [30, 40]], [[5, 10], [15, 35]]) == \
        [[0, 5], [10, 15], [35, 40]]
    assert trace.op_family("%fusion.123 = bf16[8] fusion(...)") == "fusion"
    assert trace.op_family("%copy.271 = bf16[24,2]") == "copy"


def test_reduction_of_a_hand_made_trace():
    """Two chips; known busy, idle, exposed-collective and gap figures.
    Times in ns; the window is 1000 ns."""
    ops0 = [("%fusion.1 = f32[] fusion()", 0, 400),
            ("%all-reduce.1 = f32[] all-reduce()", 300, 300),   # 200 exposed
            ("%copy.2 = f32[] copy()", 700, 100)]
    ops1 = [("%fusion.1 = f32[] fusion()", 0, 500),
            ("%all-reduce.1 = f32[] all-reduce()", 400, 300)]   # 200 exposed
    host = [("engine.step", 0, 1000), ("np.asarray", 590, 150)]
    tr = {"devices": {0: {"modules": [("jit_fn(1)", 0, 800)],
                          "ops": ops0, "async": []},
                      1: {"modules": [("jit_fn(1)", 0, 700)],
                          "ops": ops1, "async": []}},
          "host": host}
    red = trace.reduce(tr, 1000e-9)
    assert red["busy_s"] == pytest.approx((700 + 700) / 2 * 1e-9)
    assert red["exposed_collective_s"] == pytest.approx(200e-9)
    assert red["collective_s"] == pytest.approx(300e-9)
    assert dict(red["device_ops"])["fusion"] == pytest.approx(400e-9)
    # device 0 idles 600..700, inside np.asarray (the innermost span)
    assert red["idle_gaps"] == [["np.asarray", pytest.approx(100e-9)]]
    by_kind = trace.module_time_by_kind(
        red["modules"], ["decode_step"], ["jit_fn("])
    assert by_kind == {"decode_step": {"seconds": pytest.approx(800e-9),
                                       "n": 1}}
    assert trace.module_time_by_kind(red["modules"], [], ["jit_fn("]) is None


def test_reduction_of_the_recorded_chip_trace():
    """Six decode ticks of gpt2-medium recorded on a TPU v5e (PR 25):
    the figures read by hand from the profile, 88.86 ms a program
    execution, the pool copies first."""
    tr = trace.load_recorded(os.path.join(HERE, "data",
                                          "serve_decode_v5e.json.gz"))
    mods = tr["devices"][0]["modules"]
    assert len(mods) == 6
    assert all(m[0].startswith("jit_fn(") for m in mods)
    assert sum(m[2] for m in mods) / 1e6 == pytest.approx(533.126, abs=0.01)
    window = (mods[-1][1] + mods[-1][2] - mods[0][1]) / 1e9
    red = trace.reduce(tr, window)
    assert 0.95 * 0.533126 < red["busy_s"] <= 0.533126 + 1e-9
    assert red["device_ops"][0][0] == "copy"
    idle = 1.0 - red["busy_s"] / window
    assert 0.0 < idle < 0.10
    kinds = trace.module_time_by_kind(red["modules"], ["decode_step"] * 6,
                                      ["jit_fn("])
    assert kinds["decode_step"]["n"] == 6
    assert kinds["decode_step"]["seconds"] * 1e3 / 6 == pytest.approx(
        88.854, abs=0.01)


def test_a_kernel_is_read_by_its_name_in_the_trace():
    """``paged_attn_decode_roofline`` through its metric file: the
    decode ticks' live K and V rows once, queries and outputs, over the
    bandwidth, over the device time of the operations named
    ``paged_attention_decode`` (hand-made trace: 24 calls of 50 us in
    each of two decode ticks; a prefill tick and other operations do
    not count)."""
    medium = _cfg("gpt2-medium")
    reader, kw = cells.metric_reader("paged_attn_decode_roofline")
    ops = []
    for i in range(48):
        ops.append((f"%paged_attention_decode.{i % 24} = bf16[16,1024] "
                    f"custom-call()", 1000 * i, 50_000))
        ops.append((f"%fusion.{i} = bf16[16,1024] fusion()", 1000 * i, 9_000))
    ticks = [{"dispatches": ["decode_step"], "decode_batch": 16,
              "kv_tokens": 6400},
             {"dispatches": ["prefill_step"], "decode_batch": 0,
              "kv_tokens": 0},
             {"dispatches": ["prefill_step", "decode_step"],
              "decode_batch": 15, "kv_tokens": 6000}]
    ctx = {"cfg": medium, "family": counts, "trace": {"ops": ops},
           "peaks": peaks.peaks_for("TPU v5 lite"),
           "counters": {"ticks": ticks}}
    nbytes = 98_304 * (6400 + 6000) + 2 * 24 * 1024 * 2 * (16 + 15)
    assert counts.paged_attn_decode_bytes(medium, ticks[0]) \
        + counts.paged_attn_decode_bytes(medium, ticks[2]) == nbytes
    assert counts.paged_attn_decode_flops(medium, ticks[0]) == \
        4 * 24 * 1024 * 6400
    # memory-bound: bytes over 819 GB/s, against 48 x 50 us
    assert reader(ctx, **kw) == pytest.approx(
        100.0 * (nbytes / 819e9) / (48 * 50e-6))
    # nothing of that name in the trace, no trace, no decode tick, no
    # peaks (off the chip): nothing is read, never 0
    for gone in ({"trace": {"ops": [o for o in ops if "fusion" in o[0]]}},
                 {"trace": None}, {"counters": {"ticks": ticks[1:2]}},
                 {"peaks": None}):
        assert reader(dict(ctx, **gone), **kw) is None


def test_whole_step_shares_take_their_counts_from_the_family():
    """``train_mfu`` and ``decode_step_mfu`` through their metric files
    with a family that is not GPT-2's: the counts are asked of
    ``ctx["family"]``, with the tick's whole record."""
    import types
    seen = []

    def decode_step_flops(cfg, tick):
        seen.append(tick)
        return 1e9 * tick["experts_hit"]
    family = types.SimpleNamespace(
        train_flops_per_token=lambda cfg, seq_len: 2e9 + seq_len,
        decode_step_flops=decode_step_flops)
    v5e = peaks.peaks_for("TPU v5 lite")
    ctx = {"cfg": {}, "family": family, "mix": {"seq_len": 1000},
           "peaks": v5e, "window_s": 2.0,
           "counters": {"tokens_per_s": 1e4, "chips": 1, "ticks": [
               {"dispatches": ["decode_step"], "experts_hit": 3},
               {"dispatches": ["prefill_step"], "experts_hit": 9},
               {"dispatches": ["decode_step"], "experts_hit": 5}]}}
    reader, kw = cells.metric_reader("train_mfu")
    assert reader(ctx, **kw) == pytest.approx(
        100.0 * (2e9 + 1000) * 1e4 / 197e12)
    reader, kw = cells.metric_reader("decode_step_mfu")
    assert reader(ctx, **kw) == pytest.approx(100.0 * 8e9 / 2.0 / 197e12)
    assert [tk["experts_hit"] for tk in seen] == [3, 5]
    assert reader(dict(ctx, peaks=None), **kw) is None


def test_reference_sample_is_seeded_and_holds_the_longest():
    from pb import serve_common
    samples = [([1] * (5 + i % 7), [2] * (3 + (i * 5) % 11))
               for i in range(40)]
    assert serve_common.reference_sample(samples, None, BIG) is samples
    assert serve_common.reference_sample(samples, 40, BIG) is samples
    a = serve_common.reference_sample(samples, 9, BIG)
    assert a == serve_common.reference_sample(samples, 9, BIG)
    assert a != serve_common.reference_sample(samples, 9, BIG + 1)
    assert len(a) == 9 and all(x in samples for x in a)
    longest = max(len(p) + len(o) for p, o in samples)
    assert max(len(p) + len(o) for p, o in a) == longest
    assert len(serve_common.reference_sample(samples, 1, BIG)) == 1


# -- what a run reads of itself ------------------------------------------------


def test_gap_number_is_the_squared_gaps_over_the_close_calls():
    from pb import serve_common
    gaps = np.array([0.0, 0.0, 0.03, 0.01, 0.0])
    margins = np.array([0.5, 0.04, 0.03, 0.01, 0.2])        # three close
    got = serve_common.gap_numbers(gaps, margins)
    assert got == {"served_sq_gap_per_close_call":
                   pytest.approx((0.03 ** 2 + 0.01 ** 2) / 3)}
    # no call is close: the denominator is 1, never 0
    far = serve_common.gap_numbers(np.array([0.0, 2.0]), np.array([1.0, 2.0]))
    assert far["served_sq_gap_per_close_call"] == pytest.approx(4.0)
    empty = serve_common.gap_numbers(np.zeros(0), np.zeros(0))
    assert all(v != v for v in empty.values())      # NaN: judged not correct
    assert "3 calls are close" in serve_common.gap_report(gaps, margins)


def test_tick_report_names_the_longest_tick_and_the_time_between():
    from pb import serve_common
    ticks = [{"t0": 0.000, "t1": 0.090}, {"t0": 0.091, "t1": 0.181},
             {"t0": 0.183, "t1": 1.583, "dispatches": ["decode_step"]}]
    line = serve_common.tick_report(ticks, 1.6)
    assert "ticks: 3" in line and "max 1400.00 ms (tick 2: decode_step)" in line
    assert "between ticks 3.0 ms of 1600" in line
    assert serve_common.tick_report([], 1.0) == "ticks: none"


class _Chip:
    def __init__(self, **stats):
        self.stats = stats

    def memory_stats(self):
        return self.stats


@pytest.mark.parametrize("stats,total", [
    # the live arrays' peak dates from set-up: what is live while the
    # window's programs run, plus their reserve, is the peak
    (dict(bytes_in_use=4, peak_bytes_in_use=5, peak_bytes_reserved=9), 13),
    # nothing reserved (a backend that counts temporaries as in use)
    (dict(bytes_in_use=4, peak_bytes_in_use=7), 7),
    # no readings at all
    ({}, 0),
])
def test_memory_peak_is_live_now_plus_the_programs_reserve(stats, total):
    from pb.runenv import Env
    env = Env(0.0, [_Chip(**stats), _Chip()], None, "")
    assert env.memory_peak(2)["total"] == total
