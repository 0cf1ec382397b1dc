"""The yardstick's own arithmetic: the traffic generator, the FLOP and
byte counts, the trace reduction.  Nothing here needs a device."""
import json
import os

import numpy as np
import pytest

import pb_tiny  # noqa: F401  (puts perfbench/ on sys.path)
from pb import counts, peaks, trace, traffic

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
    import re
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
    assert counts.roofline_seconds(197e12, 1.0, v5e) == (1.0, "compute")
    assert counts.roofline_seconds(1.0, 819e9, v5e) == (1.0, "memory")
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
