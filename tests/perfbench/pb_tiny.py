"""A tiny copy of the benchmark's data files, for driving the harness's
own functions on the CPU: the same BENCHMARK.json entries, the same
code, sizes a test run can hold."""
import json
import os
import shutil
import sys
import types

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "perfbench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

TINY_MODEL = dict(n_embd=64, n_layer=2, n_head=4, vocab_size=211,
                  n_positions=128, n_ctx=128)


def _load(path):
    with open(path) as f:
        return json.load(f)


def _dump(path, data):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(data, f, indent=1)


def make_repo(tmp, float32=True):
    """Copy BENCHMARK.json and the data files into ``tmp`` with every
    size cut down; code is not copied (kinds and readers are copied so
    that a test can add one beside them)."""
    tmp = str(tmp)
    bench = _load(os.path.join(REPO, "BENCHMARK.json"))
    _dump(os.path.join(tmp, "BENCHMARK.json"), bench)
    for folder in ("kinds", "readers", "metrics", "workloads"):
        shutil.copytree(os.path.join(BENCH, folder),
                        os.path.join(tmp, "perfbench", folder))
    # limits for the tiny sizes (the committed ones are the chip's, set
    # from readings at the cells' own sizes): float32 serving picks the
    # reference's own token everywhere here, and the bf16 step on the CPU reads losses to 2e-5, the
    # first gradient to 3e-3 (median leaf 4e-4) and the change to 1e-2
    # (median leaf 4e-4)
    wdir = os.path.join(tmp, "perfbench", "workloads")
    for name in os.listdir(wdir):
        st = _load(os.path.join(wdir, name))
        if "served_sq_gap_per_close_call" in st["limits"]:
            st["limits"]["served_sq_gap_per_close_call"] = 1e-9
        else:
            st["limits"] = {"loss1_gap": 5e-4, "loss2_gap": 5e-4,
                            "loss3_gap": 5e-4, "grad1_gap": 0.02,
                            "grad1_median_gap": 1e-3, "delta3_gap": 0.05,
                            "delta3_median_gap": 1e-3}
        st["trace_seconds"] = 1.0
        _dump(os.path.join(wdir, name), st)
    for conf in bench["configs"]:
        cfg = _load(os.path.join(REPO, conf["file"]))
        cfg.update(TINY_MODEL)
        if "serve" in cfg:
            cfg["serve"].update(num_blocks=96, block_size=4, max_batch=4,
                                prefill_chunk=32)
            if float32:
                cfg["serve"].update(weights_dtype="float32",
                                    cache_dtype="float32")
        _dump(os.path.join(tmp, conf["file"]), cfg)
    for name in os.listdir(os.path.join(BENCH, "traffic")):
        mix = _load(os.path.join(BENCH, "traffic", name))
        if mix["kind"] == "train":
            mix.update(global_batch=8, seq_len=32, reference_block_rows=4)
        else:
            mix.update(prompt={"dist": "uniform", "lo": 6, "hi": 40},
                       output={"dist": "uniform", "lo": 4, "hi": 12},
                       max_total=96, warm_prompt_lens=[9, 30],
                       warm_prefill_lens=[], cycle=8)
        _dump(os.path.join(tmp, "perfbench", "traffic", name), mix)
    return tmp


class Counts:
    directory = "(none)"
    hits = misses = 0


def make_env(trace_dir):
    import time

    import jax
    from pb.runenv import Env
    return Env(time.perf_counter(), jax.devices(), Counts(), str(trace_dir))


def args(seed=7, seconds=1.0, trace=0):
    return types.SimpleNamespace(seed=seed, seconds=seconds, trace=trace)
