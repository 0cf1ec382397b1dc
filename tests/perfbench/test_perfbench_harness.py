"""The harness's own functions driven on the CPU at tiny sizes: every
traffic kind end to end, a cell and a metric added as new files, the
control that has to come out as not correct, and the timed path broken
underneath."""
import ast
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
    before = pb_tiny.files_under(os.path.join(repo, "perfbench"))
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


# -- a model family is a file ---------------------------------------------------

ADDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "added_family")
#: the added cells: configuration, mix, the cell whose limits file and
#: metrics they take, their end-to-end metric
ADDED_CELLS = {
    "biasgpt-train": ("biasgpt-mini", "train-16x1024", "gpt2s-train",
                      "train_tokens_per_s"),
    "biasgpt-serve": ("biasgpt-mini", "short-in-long-out-closed",
                      "gpt2m-serve-decode", "serve_tokens_per_s"),
    "ropedec-train": ("ropedec-mini", "train-16x1024", "gpt2s-train",
                      "train_tokens_per_s")}


#: the reader of the added per-layer metrics: the added families' counts
ADDED_COUNTS = """\
def train_flops_per_token(ctx):
    return ctx['family'].train_flops_per_token(
        ctx['cfg'], ctx['mix']['seq_len'])


def decode_flops(ctx):
    return sum(ctx['family'].decode_step_flops(ctx['cfg'], tk)
               for tk in ctx['counters']['ticks']
               if 'decode_step' in tk['dispatches'])
"""


@pytest.fixture(scope="module")
def added_family(tmp_path_factory):
    """A checkout's benchmark as a later ``model_config`` PR leaves it:
    everything that was there, and beside it two families of other keys
    and other blocks (``data/added_family/``: ``biasgpt``, q/k/v/out
    biases, a sliced vocabulary, (out, in) matrices, trained and
    served; ``ropedec``, RMSNorm, rotary positions, grouped keys and
    values, a gated feed-forward and an untied head, trained only: the
    engine refuses rotary blocks), their references, their
    configurations, cells of both kinds on the mixes that are there,
    and two per-layer metrics fed by the families' counts.  Returns the
    checkout, the tiny copy the cells run from, and what the files held
    before."""
    files = {"readers/added_counts.py": ADDED_COUNTS}
    for fam in ("biasgpt", "ropedec"):
        for name, to in [(f"{fam}.py", f"families/{fam}.py"),
                         (f"{fam}_reference.py", f"references/{fam}.py"),
                         (f"{fam}-mini.json", f"configs/{fam}-mini.json")]:
            with open(os.path.join(ADDED, name)) as f:
                files[to] = f.read()
    configs = [{"name": f"{fam}-mini",
                "source": "tests/perfbench/data/added_family",
                "file": f"perfbench/configs/{fam}-mini.json", "reduced": [],
                "why": "added by a test"} for fam in ("biasgpt", "ropedec")]
    added = [({"name": name, "config": config, "traffic": mix, "chips": 1,
               "why": "added by a test"}, like)
             for name, (config, mix, like, _) in ADDED_CELLS.items()]
    metrics = [({"name": "added_" + reader, "unit": "flops",
                 "better": "lower", "source": "program_counter",
                 "layer": "step programs", "moves": e2e,
                 "workloads": [n for n, c in ADDED_CELLS.items()
                               if c[3] == e2e]},
                {"reader": "added_counts." + reader, "args": {}})
               for reader, e2e in [("train_flops_per_token",
                                    "train_tokens_per_s"),
                                   ("decode_flops", "serve_tokens_per_s")]]
    # the added families have no count of the paged reader's bytes
    src, before = pb_tiny.model_config_pr(
        tmp_path_factory.mktemp("src"), files, configs, added, metrics,
        not_shared={"paged_attn_decode_roofline"})
    tiny = pb_tiny.make_repo(tmp_path_factory.mktemp("tiny"), source=src)
    yield src, tiny, before
    from apex_tpu.runtime import step_cache
    step_cache.clear()


#: by hand, at the families' tiny sizes: the parameters that sit in a
#: matrix product for every token
HAND_MATMUL = {
    # 2 layers x (qkv 3 x 48 x 48 + out 48 x 48 + 2 x 48 x 80) + head 302 x 48
    "biasgpt": 2 * (4 * 48 * 48 + 2 * 48 * 80) + 302 * 48,
    # 2 layers x (q, o 32 x 32; k, v 16 x 32; gate, up, down 32 x 56) + 173 x 32
    "ropedec": 2 * (2 * 32 * 32 + 2 * 16 * 32 + 3 * 32 * 56) + 173 * 32}


@pytest.mark.parametrize("name", sorted(ADDED_CELLS))
def test_a_family_of_other_keys_comes_in_as_files_alone(added_family, name):
    """A ``train`` and a ``closed`` cell of an added family, plain and
    traced on the CPU, at the family's tiny sizes and not at its file's;
    ``correct`` by the family's own reference; a per-layer metric fed by
    the family's own counts; and no file that was there edited."""
    src, tiny, before = added_family
    fam = ADDED_CELLS[name][0][:-len("-mini")]
    cell, line = _run(tiny, name)
    family, cfg = cell.family, cell.config
    assert family.__file__ == os.path.join(
        tiny, "perfbench", "families", fam + ".py")
    assert cell.reference.__file__.endswith(f"references/{fam}.py")
    # the family's tiny sizes, not its file's
    at_file = pb_tiny._load(os.path.join(ADDED, fam + "-mini.json"))
    for key, value in family.tiny(at_file).items():
        assert cfg[key] == value != at_file[key], key
    if fam == "biasgpt":            # a sliced vocabulary
        assert family.vocab(cfg) == 1208 // 4 == 302
    assert not set(cfg) & {"n_embd", "n_layer", "n_head", "n_positions",
                           "vocab_size"}
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {ADDED_CELLS[name][3], "setup_s"}
    assert all(v["value"] > 0 for v in line["metrics"].values())
    cell, line = _run(tiny, name, trace=1, seconds=3.0)
    assert line["correct"] is True, line["compared"]
    assert not [m for m in line["metrics"] if m.endswith(DEVICE_METRIC)]
    matmul = HAND_MATMUL[fam]
    assert family.matmul_params(cfg) == matmul
    if cell.kind == "train":
        seq, width = cell.traffic["seq_len"], {"biasgpt": 48, "ropedec": 32}
        assert line["metrics"]["added_train_flops_per_token"]["value"] == \
            6 * matmul + 3 * 4 * 2 * width[fam] * (seq + 1) / 2
        assert line["metrics"]["train_compiles_in_window"]["value"] == 0
    else:
        # every decode tick: 2 x matmul parameters a session at least
        got = line["metrics"]["added_decode_flops"]["value"]
        assert got > 2 * matmul * line["metrics"]["decode_batch_mean"]["value"]
    assert pb_tiny.files_under(os.path.join(src, "perfbench")).items() >= \
        before.items(), "a file that was there was edited"
    untouched = cells.load_benchmark()
    now = cells.load_benchmark(src)
    for key in ("configs", "workloads"):
        assert now[key][:len(untouched[key])] == untouched[key], key
        assert len(now[key]) > len(untouched[key]), key


def test_the_added_familys_control_and_fault_are_not_correct(added_family):
    """The seam carries the rest of what decides ``correct``: the added
    family's reference one precision down fails its train cell, and a
    token altered where it is produced fails its serve cell."""
    _, tiny, _ = added_family
    cell = cells.Cell("biasgpt-train", repo=tiny)
    train = cells.kind_module("train", tiny)
    ref = train.reference_readings(cell, SEEDS[1])
    ctl = train.reference_readings(cell, SEEDS[1], quant="int8")
    ok, compared = correct.judge(correct.train_numbers(ctl, ref),
                                 cell.settings["limits"])
    assert not ok, compared

    def fault(loop):
        def alter(tr, s):
            if len(s.out) == 2 and not getattr(s, "_altered", False):
                s._altered = True
                s.out[-1] = s.pending_tok = (s.out[-1] + 1) % 302
        loop.on_token = alter
    _, line = _run(tiny, "biasgpt-serve", fault=fault)
    assert line["correct"] is False


def test_a_builder_that_names_no_file_is_a_clear_error(repo):
    path = os.path.join(repo, "perfbench", "configs", "gpt2-small.json")
    cfg = pb_tiny._load(path)
    try:
        for builder in ("gpt3", None):
            pb_tiny._dump(path, dict(cfg, builder=builder))
            with pytest.raises(SystemExit) as e:
                cells.Cell("gpt2s-train", repo=repo).family
            assert "perfbench/families/" in str(e.value)
            assert repr(builder) in str(e.value) and "'gpt'" in str(e.value)
        pb_tiny._dump(path, dict(cfg, reference="perfbench/pb/nowhere.py"))
        with pytest.raises(SystemExit, match="nowhere.py"):
            cells.Cell("gpt2s-train", repo=repo).reference
    finally:
        pb_tiny._dump(path, cfg)


def test_the_reference_key_is_what_finds_the_reference(repo):
    """Pointed at a file whose losses are wrong, the train cell is not
    ``correct``: the key is read, and nothing else decides which
    reference a cell is compared with."""
    path = os.path.join(repo, "perfbench", "configs", "gpt2-small.json")
    cfg = pb_tiny._load(path)
    wrong = os.path.join(repo, "perfbench", "pb", "wrong_reference.py")
    with open(wrong, "w") as f:
        f.write(
            "import importlib.util, os\n"
            "_spec = importlib.util.spec_from_file_location(\n"
            "    'right_reference', os.path.join(os.path.dirname(__file__), "
            "'reference.py'))\n"
            "_right = importlib.util.module_from_spec(_spec)\n"
            "_spec.loader.exec_module(_right)\n\n\n"
            "def train_reference(*a, **kw):\n"
            "    out = _right.train_reference(*a, **kw)\n"
            "    out['losses'] = [1.01 * l for l in out['losses']]\n"
            "    return out\n")
    try:
        pb_tiny._dump(path, dict(
            cfg, reference="perfbench/pb/wrong_reference.py"))
        _, line = _run(repo, "gpt2s-train")
        assert line["correct"] is False
        value, limit = line["compared"]["loss2_gap"]
        assert value == pytest.approx(0.01, rel=0.05) and value > limit
    finally:
        pb_tiny._dump(path, cfg)
        os.remove(wrong)


def _imports_of(path):
    with open(path) as f:
        tree = ast.parse(f.read())
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            yield from (a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            yield "." * node.level + (node.module or "")


@pytest.mark.parametrize("conf", cells.load_benchmark()["configs"],
                         ids=lambda c: c["name"])
def test_the_reference_imports_nothing_of_the_program(conf):
    """By its source: the configuration's reference file, and what it
    shares with every other reference, import jax, the standard library
    and ``pb.refmath`` alone."""
    cfg = pb_tiny._load(os.path.join(pb_tiny.REPO, conf["file"]))
    allowed = {"__future__", "functools", "json", "math", "jax",
               "jax.numpy", "pb.refmath"}
    for path in (os.path.join(pb_tiny.REPO, cfg["reference"]),
                 os.path.join(pb_tiny.BENCH, "pb", "refmath.py"),
                 os.path.join(ADDED, "biasgpt_reference.py"),
                 os.path.join(ADDED, "ropedec_reference.py")):
        assert set(_imports_of(path)) <= allowed, path


def test_only_the_family_and_the_reference_know_the_model():
    """Outside the families' files and the references the configurations
    are compared with, nothing under ``perfbench/`` and nothing in
    ``pb_tiny.py`` reads a GPT-2 size, names a GPT-2 leaf or imports the
    program's models; and ``builder`` and ``reference`` are read in one
    place."""
    import re
    knows = re.compile(r"n_embd|n_layer|n_head|n_positions|n_inner|c_attn|"
                       r"c_proj|c_fc|\bwte\b|\bwpe\b|apex_tpu\.models|"
                       r"GptModel")
    keys = re.compile(r"""["'](builder|reference)["']""")
    families = os.path.join(pb_tiny.BENCH, "families")
    may_know = {os.path.join(families, f) for f in os.listdir(families)} | {
        os.path.join(pb_tiny.REPO, pb_tiny._load(
            os.path.join(pb_tiny.REPO, conf["file"]))["reference"])
        for conf in cells.load_benchmark()["configs"]}
    files = [os.path.join(root, f)
             for root, _, fs in os.walk(pb_tiny.BENCH) for f in fs
             if f.endswith(".py")] + [pb_tiny.__file__]
    readers_of_the_keys = []
    for path in files:
        with open(path) as f:
            code = f.read()
        if path not in may_know:
            assert not knows.search(code), (path, knows.search(code).group())
        if keys.search(code):
            readers_of_the_keys.append(os.path.relpath(path, pb_tiny.REPO))
    # cells.py finds the family and the reference; pb_tiny.py copies the
    # reference file beside the tiny configuration and asks cells for
    # the family
    assert sorted(readers_of_the_keys) == [
        "perfbench/pb/cells.py", "tests/perfbench/pb_tiny.py"]


# -- the control: the reference one precision down has to fail -------------


@pytest.mark.parametrize("quant", ["int8", "fp8"])
@pytest.mark.parametrize("seed", SEEDS)
def test_train_control_is_not_correct(repo, seed, quant):
    """The reference computed one precision down, put in the program's
    place: the comparison has to refuse it."""
    cell = cells.Cell("gpt2s-train", repo=repo)
    train = cells.kind_module("train", repo)
    ref = train.reference_readings(cell, seed)
    ctl = train.reference_readings(cell, seed, quant=quant)
    numbers = correct.train_numbers(ctl, ref)
    ok, compared = correct.judge(numbers, cell.settings["limits"])
    assert not ok, compared


@pytest.mark.parametrize("quant", ["int8", "fp8"])
@pytest.mark.parametrize("seed", SEEDS)
def test_serve_control_is_not_correct(repo, seed, quant):
    """The lower-precision reference's own first choices, at the
    positions of served prompts and tokens, judged as a run's are."""
    cell = cells.Cell("gpt2m-serve-decode", repo=repo)
    vocab = cell.family.vocab(cell.config)
    rng = np.random.default_rng(seed)
    def toks(n):
        return [int(t) for t in rng.integers(1, vocab, n)]
    samples = [(toks(30 + i), toks(80)) for i in range(6)]
    gaps, margins = serve_common.served_gaps(cell, seed, samples,
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
    cell = cells.Cell(name, repo=repo)
    vocab = cell.family.vocab(cell.config)

    def fault(loop):
        def alter(tr, s):
            if len(s.out) == 2 and not getattr(s, "_altered", False):
                s._altered = True
                s.out[-1] = s.pending_tok = (s.out[-1] + 1) % vocab
        loop.on_token = alter
    _, line = _run(repo, name, fault=fault)
    assert line["correct"] is False
    value, limit = line["compared"]["served_sq_gap_per_close_call"]
    assert value > 1e3 * limit
