"""The harness's own functions driven on the CPU at tiny sizes: every
traffic kind end to end, a cell and a metric added as new files, the
control that has to come out as not correct, and the timed path broken
underneath."""
import json
import os

import numpy as np
import pytest

import pb_tiny
from pb import cells, correct, serve_common
import run as pbrun

SEEDS = [2 ** 31 + 5, 11, 4_100_000_123]
DEVICE_METRIC = ("_device_ms", "_mfu", "_roofline", "_idle_share")


@pytest.fixture(scope="module")
def repo(tmp_path_factory):
    yield pb_tiny.make_repo(tmp_path_factory.mktemp("pb"))
    # every run here builds a new step or engine, each with programs of
    # its own: leave the process-wide LRU of step programs empty, or a
    # test file that a worker runs after this one starts at its cap
    # (tests/test_jaxpr_audit.py slices the cache's entries by index)
    from apex_tpu.runtime import step_cache
    step_cache.clear()


def _run(repo, name, trace=0, seed=SEEDS[0], seconds=1.0, **fault):
    cell = cells.Cell(name, repo=repo)
    env = pb_tiny.make_env(os.path.join(repo, ".trace"))
    args = pb_tiny.args(seed=seed, seconds=seconds, trace=trace)
    kind = cells.kind_module(cell.kind, repo)
    result = kind.run(cell, args, env, **fault)
    return cell, pbrun.result_line(cell, args, result, env)


CELLS = [w["name"] for w in cells.load_benchmark()["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_cell_runs_on_the_cpu_and_reports_end_to_end(repo, name):
    cell, line = _run(repo, name)
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert "setup_s" in line["metrics"] and len(line["metrics"]) >= 2
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert line["device"]["platform"] == "cpu"
    assert list(line)[-1] == "compared"
    json.dumps(line)


@pytest.mark.parametrize("name", CELLS)
def test_traced_cell_prints_no_device_metric_off_the_chip(repo, name):
    # --seconds over twice the traced window: a serving cell serves on
    # after it, so that as many tokens are compared as without a trace
    cell, line = _run(repo, name, trace=1, seconds=3.0)
    wanted = {m["name"] for m in cell.per_layer}
    assert set(line["metrics"]) <= wanted and line["metrics"]
    assert not [m for m in line["metrics"] if m.endswith(DEVICE_METRIC)]
    assert "busy_s" not in line["device"] and "breakdown" not in line
    assert line["correct"] is True


def test_new_cell_and_metric_are_found_as_new_files(repo):
    """A later PR adds entries and files and edits none that is there."""
    before = {}
    for root, _, files in os.walk(os.path.join(repo, "perfbench")):
        for f in files:
            p = os.path.join(root, f)
            before[p] = open(p, "rb").read()
    bench = cells.load_benchmark(repo)
    bench["workloads"].append({
        "name": "added-cell", "config": "gpt2-small",
        "traffic": "train-added", "chips": 1, "why": "added by a test"})
    bench["end_to_end"][0]["workloads"].append("added-cell")
    bench["per_layer"].append({
        "name": "added_steps", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "entry and executor",
        "moves": "train_tokens_per_s", "workloads": ["added-cell"]})
    with open(os.path.join(repo, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    pb = os.path.join(repo, "perfbench")
    pb_tiny._dump(os.path.join(pb, "traffic", "train-added.json"), {
        "kind": "train", "global_batch": 4, "seq_len": 16,
        "parallel": "single", "in_flight": 1})
    st = pb_tiny._load(os.path.join(pb, "workloads", "gpt2s-train.json"))
    pb_tiny._dump(os.path.join(pb, "workloads", "added-cell.json"), st)
    pb_tiny._dump(os.path.join(pb, "metrics", "added_steps.json"),
                  {"reader": "added.steps", "args": {"scale": 2}})
    with open(os.path.join(pb, "readers", "added.py"), "w") as f:
        f.write("def steps(ctx, scale):\n"
                "    return scale * ctx['counters']['steps']\n")
    cell, line = _run(repo, "added-cell", trace=1)
    assert line["metrics"]["added_steps"]["value"] == 2 * line["attempted"]
    _, line = _run(repo, "added-cell", trace=0)
    assert line["correct"] and "train_tokens_per_s" in line["metrics"]
    for p, data in before.items():
        assert open(p, "rb").read() == data, f"{p} was edited"


def test_four_chip_cell_is_added_as_files_and_agrees_on_the_cpu(repo):
    """The cell PR 25 left out (PERF.md section 7) comes in with entries
    and two data files: 4 rows a device over four virtual CPU devices
    through the library's own data-parallel entry.  Here attention is
    XLA's, so the entry the TPU compiler refuses runs, and it agrees
    with the reference: the witness that the fault is the program's."""
    bench = cells.load_benchmark(repo)
    bench["workloads"].append({
        "name": "added-dp4", "config": "gpt2-small",
        "traffic": "train-added-dp4", "chips": 4, "why": "added by a test"})
    bench["end_to_end"][0]["workloads"].append("added-dp4")
    with open(os.path.join(repo, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    pb = os.path.join(repo, "perfbench")
    pb_tiny._dump(os.path.join(pb, "traffic", "train-added-dp4.json"), {
        "kind": "train", "global_batch": 16, "seq_len": 32,
        "parallel": "dp", "in_flight": 2, "reference_block_rows": 4})
    st = pb_tiny._load(os.path.join(pb, "workloads", "gpt2s-train.json"))
    pb_tiny._dump(os.path.join(pb, "workloads", "added-dp4.json"), st)
    cell, line = _run(repo, "added-dp4")
    assert cell.chips == 4 and line["device"]["count"] == 4
    assert line["correct"] is True, line["compared"]
    assert line["metrics"]["train_tokens_per_s"]["value"] > 0


# -- the control: the reference one precision down has to fail -------------


@pytest.mark.parametrize("quant", ["int8", "fp8"])
@pytest.mark.parametrize("seed", SEEDS)
def test_train_control_is_not_correct(repo, seed, quant):
    """The reference computed one precision down, put in the program's
    place: the comparison has to refuse it."""
    cell = cells.Cell("gpt2s-train", repo=repo)
    train = cells.kind_module("train", repo)
    ref = train.reference_readings(cell.config, cell.traffic, seed)
    ctl = train.reference_readings(cell.config, cell.traffic, seed,
                                   quant=quant)
    numbers = correct.train_numbers(ctl, ref)
    ok, compared = correct.judge(numbers, cell.settings["limits"])
    assert not ok, compared


@pytest.mark.parametrize("quant", ["int8", "fp8"])
@pytest.mark.parametrize("seed", SEEDS)
def test_serve_control_is_not_correct(repo, seed, quant):
    """The lower-precision reference's own first choices, at the
    positions of served prompts and tokens, judged as a run's are."""
    cell = cells.Cell("gpt2m-serve-decode", repo=repo)
    cfg = cell.config
    rng = np.random.default_rng(seed)
    def toks(n):
        return [int(t) for t in rng.integers(1, cfg["vocab_size"], n)]
    samples = [(toks(30 + i), toks(80)) for i in range(6)]
    gaps, margins = serve_common.served_gaps(cfg, seed, samples,
                                             control=quant)
    # every token of every request, and no gap without a flip
    assert len(gaps) == len(margins) == 480
    assert (gaps >= 0).all() and (gaps[gaps > 0] >= margins[gaps > 0]).all()
    ok, compared = correct.judge(serve_common.gap_numbers(gaps, margins),
                                 cell.settings["limits"])
    assert not ok, compared


# -- the timed path broken underneath: correct has to come out false --------


def _unchanged_state(step):
    def call(x, y):
        keep = step.state
        loss = step(x, y)
        step.state = keep
        return loss
    return call


def _rows(share):
    def wrapper(step):
        def call(x, y):
            n = max(1, int(x.shape[0] * share))
            return step(x[:n], y[:n])
        return call
    return wrapper


@pytest.mark.parametrize("fault,why", [
    (_unchanged_state, "a step that returns its state unchanged"),
    (_rows(0.5), "half of the batch left out, the mean over the rest"),
    (_rows(0.25), "the exchange between four chips left out: one "
                  "shard's rows alone"),
], ids=["state_unchanged", "half_batch", "no_exchange"])
def test_broken_train_step_is_not_correct(repo, fault, why):
    _, line = _run(repo, "gpt2s-train", step_call_wrapper=fault)
    assert line["correct"] is False, why


@pytest.mark.parametrize("name", ["gpt2m-serve-decode"])
def test_altered_token_is_not_correct(repo, name):
    def fault(loop):
        def alter(tr, s):
            if len(s.out) == 2 and not getattr(s, "_altered", False):
                s._altered = True
                s.out[-1] = s.pending_tok = (s.out[-1] + 1) % 211
        loop.on_token = alter
    _, line = _run(repo, name, fault=fault)
    assert line["correct"] is False
    value, limit = line["compared"]["served_sq_gap_per_close_call"]
    assert value > 1e3 * limit
