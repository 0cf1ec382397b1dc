"""Finding a cell's files by the names in ``BENCHMARK.json``.

A cell is one entry of ``workloads``: a configuration under a traffic
mix.  Whatever belongs to one configuration, one mix, one cell or one
per-layer metric is a file of its own:

    perfbench/configs/<config>.json    sizes as run (named by ``file``)
    perfbench/traffic/<mix>.json       parameters of the mix and its kind
    perfbench/workloads/<cell>.json    the cell's limits for ``correct``
    perfbench/metrics/<metric>.json    which reader computes the metric

so a later PR adds a cell, a metric or a model by adding files and
entries.  Traffic kinds (``perfbench/kinds/<kind>.py``), metric readers
(``perfbench/readers/<module>.py``) and model families
(``perfbench/families/<builder>.py``) are looked up by the name the
data file gives, never switched on a cell's name.  A configuration file
names its family (``builder``) and its plain reference (``reference``,
a path from the root of the checkout); this module is the one place
that reads either key.
"""
from __future__ import annotations

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH_DIR)


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(repo: str = REPO) -> dict:
    return _load(os.path.join(repo, "BENCHMARK.json"))


class Cell:
    def __init__(self, name: str, repo: str = REPO):
        bench = load_benchmark(repo)
        entry = next((w for w in bench["workloads"] if w["name"] == name),
                     None)
        if entry is None:
            raise SystemExit(
                f"perfbench: no workload {name!r} in BENCHMARK.json; it "
                f"has {[w['name'] for w in bench['workloads']]}")
        conf = next(c for c in bench["configs"]
                    if c["name"] == entry["config"])
        bdir = os.path.join(repo, "perfbench")
        self.repo = repo
        self.bench = bench
        self.name = name
        self.chips = int(entry["chips"])
        self.config_name = entry["config"]
        self.traffic_name = entry["traffic"]
        self.config = _load(os.path.join(repo, conf["file"]))
        self.traffic = _load(os.path.join(
            bdir, "traffic", entry["traffic"] + ".json"))
        self.settings = _load(os.path.join(
            bdir, "workloads", name + ".json"))
        self.kind = self.traffic["kind"]

    @property
    def family(self):
        """What knows this configuration's model: the module
        ``perfbench/families/<builder>.py`` (sizes, weights, the
        program's model, counts)."""
        return family_module(self.config.get("builder"), self.repo)

    @property
    def reference(self):
        """The configuration's plain reference, the file its
        ``reference`` key names: ``train_reference`` and
        ``served_token_gaps``.  It imports nothing of the program."""
        path = self.config.get("reference")
        if not path or not os.path.isfile(os.path.join(self.repo, path)):
            raise SystemExit(
                f"perfbench: configuration {self.config_name} names the "
                f"reference {path!r}, which is no file of the checkout")
        return _module_from(os.path.join(self.repo, path), "reference")

    def _reports(self, metric: dict) -> bool:
        return "workloads" not in metric or self.name in metric["workloads"]

    @property
    def end_to_end(self) -> list:
        return [m for m in self.bench["end_to_end"] if self._reports(m)]

    @property
    def per_layer(self) -> list:
        e2e = {m["name"] for m in self.end_to_end}
        return [m for m in self.bench["per_layer"]
                if self._reports(m) and m["moves"] in e2e]


_MODULES = {}


def _module_from(path: str, what: str):
    if path not in _MODULES:
        tag = f"perfbench_{what}_{len(_MODULES)}"
        spec = importlib.util.spec_from_file_location(tag, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _MODULES[path] = mod
    return _MODULES[path]


def _module_at(repo: str, folder: str, name: str):
    """Load ``<repo>/perfbench/<folder>/<name>.py`` by its path, so that
    a file a later PR adds is found with no edit to a list."""
    path = os.path.join(repo, "perfbench", folder, name + ".py")
    if not os.path.exists(path):
        raise SystemExit(f"perfbench: no {folder[:-1]} file {path}")
    return _module_from(path, f"{folder}_{name}")


def kind_module(kind: str, repo: str = REPO):
    """``perfbench/kinds/<kind>.py``, which has
    ``run(cell, args, env) -> dict``."""
    return _module_at(repo, "kinds", kind)


def family_module(builder, repo: str = REPO):
    """``perfbench/families/<builder>.py``: one model family's sizes,
    seeded weights, program model and counts (``families/gpt.py`` says
    what a family file provides)."""
    fdir = os.path.join(repo, "perfbench", "families")
    if not builder or not os.path.exists(
            os.path.join(fdir, f"{builder}.py")):
        have = sorted(f[:-3] for f in os.listdir(fdir) if f.endswith(".py"))
        raise SystemExit(
            f"perfbench: a configuration's `builder` has to name a file "
            f"of perfbench/families/; it says {builder!r} and the "
            f"families there are {have}")
    return _module_at(repo, "families", builder)


def metric_reader(name: str, repo: str = REPO):
    """The reader of one per-layer metric and its arguments, from
    ``perfbench/metrics/<name>.json``:
    ``{"reader": "<module>.<function>", "args": {...}}``; the module is
    ``perfbench/readers/<module>.py``."""
    spec = _load(os.path.join(repo, "perfbench", "metrics", name + ".json"))
    mod, fn = spec["reader"].rsplit(".", 1)
    return getattr(_module_at(repo, "readers", mod), fn), \
        spec.get("args", {})
