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

#: the engine's settings at sizes a CPU test can hold: the same keys of
#: the ``serve`` block whatever the family
TINY_SERVE = dict(num_blocks=96, block_size=4, max_batch=4, prefill_chunk=32)


def _load(path):
    with open(path) as f:
        return json.load(f)


def _dump(path, data):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(data, f, indent=1)


def tiny_config(cfg, repo, float32=True):
    """``cfg`` at the sizes its own family gives for a CPU test."""
    from pb import cells
    cfg = dict(cfg)
    cfg.update(cells.family_module(cfg.get("builder"), repo).tiny(cfg))
    if "serve" in cfg:
        cfg["serve"] = dict(cfg["serve"], **TINY_SERVE)
        if float32:
            cfg["serve"].update(weights_dtype="float32",
                                cache_dtype="float32")
    return cfg


def make_repo(tmp, float32=True, source=REPO):
    """Copy ``source``'s BENCHMARK.json and data files into ``tmp`` with
    every size cut down: each configuration by its family's ``tiny()``,
    each mix and each cell's limits by their kind's.  Code is not
    copied (kinds, readers, families and the references the
    configurations name are, so that a test can add one beside them)."""
    from pb import cells
    tmp = str(tmp)
    bdir = os.path.join(source, "perfbench")
    bench = _load(os.path.join(source, "BENCHMARK.json"))
    _dump(os.path.join(tmp, "BENCHMARK.json"), bench)
    for folder in ("kinds", "readers", "families", "metrics", "workloads"):
        shutil.copytree(os.path.join(bdir, folder),
                        os.path.join(tmp, "perfbench", folder),
                        ignore=shutil.ignore_patterns("__pycache__"))
    for conf in bench["configs"]:
        cfg = _load(os.path.join(source, conf["file"]))
        os.makedirs(os.path.dirname(os.path.join(tmp, cfg["reference"])),
                    exist_ok=True)
        shutil.copy(os.path.join(source, cfg["reference"]),
                    os.path.join(tmp, cfg["reference"]))
        _dump(os.path.join(tmp, conf["file"]), tiny_config(cfg, tmp, float32))
    mixes = {name[:-len(".json")]: _load(os.path.join(bdir, "traffic", name))
             for name in os.listdir(os.path.join(bdir, "traffic"))}
    builders = {c["name"]: _load(os.path.join(tmp, c["file"]))["builder"]
                for c in bench["configs"]}
    for cell in bench["workloads"]:
        mix = mixes[cell["traffic"]]
        wfile = os.path.join(tmp, "perfbench", "workloads",
                             cell["name"] + ".json")
        st = _load(wfile)
        tiny_mix, st["limits"] = cells.kind_module(mix["kind"], tmp).tiny(
            mix, st["limits"])
        # a family whose tiny model reads otherwise than the kind's
        # limits allow says so itself
        family = cells.family_module(builders[cell["config"]], tmp)
        if hasattr(family, "tiny_limits"):
            st["limits"] = family.tiny_limits(mix["kind"], st["limits"])
        st["trace_seconds"] = 1.0
        _dump(wfile, st)
        _dump(os.path.join(tmp, "perfbench", "traffic",
                           cell["traffic"] + ".json"), tiny_mix)
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
