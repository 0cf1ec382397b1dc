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


def files_under(path):
    """Every file under ``path`` and the bytes it holds."""
    out = {}
    for root, _, files in os.walk(path):
        for f in files:
            with open(os.path.join(root, f), "rb") as fh:
                out[os.path.join(root, f)] = fh.read()
    return out


def model_config_pr(tmp, files=None, configs=(), cells=(), metrics=(),
                    not_shared=(), source=REPO):
    """``source``'s BENCHMARK.json and ``perfbench/`` copied into ``tmp``
    as a later ``model_config`` PR leaves them: it adds files and appends
    entries, and edits no file that is there.

    - ``files``: ``{path under perfbench/: text}``, written beside the
      others;
    - ``configs``: entries appended after the last configuration;
    - ``cells``: ``(entry, like)`` pairs: the entry appended after the
      last cell, cell ``like``'s limits file copied under its name, and
      its name appended to every metric that lists ``like`` but those
      named in ``not_shared``;
    - ``metrics``: ``(entry, spec)`` pairs: the entry appended after the
      last per-layer metric, ``spec`` its ``perfbench/metrics/`` file.

    Returns the copy and what its files held before anything was added."""
    tmp = str(tmp)
    pb = os.path.join(tmp, "perfbench")
    shutil.copy(os.path.join(source, "BENCHMARK.json"), tmp)
    shutil.copytree(os.path.join(source, "perfbench"), pb,
                    ignore=shutil.ignore_patterns("__pycache__", ".trace"))
    before = files_under(pb)
    for path, text in (files or {}).items():
        os.makedirs(os.path.dirname(os.path.join(pb, path)), exist_ok=True)
        with open(os.path.join(pb, path), "w") as f:
            f.write(text)
    bench = _load(os.path.join(tmp, "BENCHMARK.json"))
    bench["configs"].extend(configs)
    for entry, like in cells:
        bench["workloads"].append(entry)
        shutil.copy(os.path.join(pb, "workloads", like + ".json"),
                    os.path.join(pb, "workloads", entry["name"] + ".json"))
        for m in bench["end_to_end"] + bench["per_layer"]:
            if like in m.get("workloads", []) and m["name"] not in not_shared:
                m["workloads"].append(entry["name"])
    for entry, spec in metrics:
        bench["per_layer"].append(entry)
        _dump(os.path.join(pb, "metrics", entry["name"] + ".json"), spec)
    _dump(os.path.join(tmp, "BENCHMARK.json"), bench)
    return tmp, before


def added_checkout(tmp, source=REPO):
    """``source`` as the next ``model_config`` PR may leave it: after the
    last configuration a copy of each under another name, after the last
    cell one like each on that copy, listed wherever its like is listed
    (nothing runs here, so every shared metric takes it), and for each
    end-to-end metric of some cells a per-layer metric of the added ones,
    with its file.  A test of the declarations holds here as it holds on
    the checkout: it pins no length, order or end of a list."""
    bench = _load(os.path.join(source, "BENCHMARK.json"))
    files, configs = {}, []
    for conf in bench["configs"]:
        name = "added-" + conf["name"]
        with open(os.path.join(source, conf["file"])) as f:
            files[f"configs/{name}.json"] = f.read()
        configs.append(dict(conf, name=name,
                            file=f"perfbench/configs/{name}.json"))
    cells = [(dict(w, name="added-" + w["name"],
                   config="added-" + w["config"]), w["name"])
             for w in bench["workloads"]]
    metrics = [({"name": "added_compiles_in_window." + m["name"],
                 "unit": "compiles", "better": "lower",
                 "source": "program_counter", "layer": "entry and executor",
                 "moves": m["name"],
                 "workloads": ["added-" + w for w in m["workloads"]]},
                {"reader": "counters.compiles_in_window", "args": {}})
               for m in bench["end_to_end"] if "workloads" in m]
    return model_config_pr(tmp, files, configs, cells, metrics,
                           source=source)[0]


#: the checkouts a test of the declarations reads: this one, and a copy as
#: the next ``model_config`` PR may leave it
CHECKOUTS = ["checkout", "added"]


def checkout(which, tmp):
    """This checkout, or (``"added"``) ``added_checkout`` in ``tmp``."""
    return REPO if which == "checkout" else added_checkout(tmp)


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
