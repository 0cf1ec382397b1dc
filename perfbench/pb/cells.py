"""Finding a cell's files by the names in ``BENCHMARK.json``.

A cell is one entry of ``workloads``: a configuration under a traffic
mix.  Whatever belongs to one configuration, one mix, one cell or one
per-layer metric is a file of its own:

    perfbench/configs/<config>.json    sizes as run (named by ``file``)
    perfbench/traffic/<mix>.json       parameters of the mix and its kind
    perfbench/workloads/<cell>.json    the cell's limits for ``correct``
    perfbench/metrics/<metric>.json    which reader computes the metric

so a later PR adds a cell or a metric by adding files and entries.
Traffic kinds (``perfbench/kinds/<kind>.py``) and metric readers
(``perfbench/readers/<module>.py``) are looked up by the name the data
file gives, never switched on a cell's name.
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


def _module_at(repo: str, folder: str, name: str):
    """Load ``<repo>/perfbench/<folder>/<name>.py`` by its path, so that
    a file a later PR adds is found with no edit to a list."""
    path = os.path.join(repo, "perfbench", folder, name + ".py")
    if path not in _MODULES:
        if not os.path.exists(path):
            raise SystemExit(f"perfbench: no {folder[:-1]} file {path}")
        tag = f"perfbench_{folder}_{name}_{len(_MODULES)}"
        spec = importlib.util.spec_from_file_location(tag, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _MODULES[path] = mod
    return _MODULES[path]


def kind_module(kind: str, repo: str = REPO):
    """``perfbench/kinds/<kind>.py``, which has
    ``run(cell, args, env) -> dict``."""
    return _module_at(repo, "kinds", kind)


def metric_reader(name: str, repo: str = REPO):
    """The reader of one per-layer metric and its arguments, from
    ``perfbench/metrics/<name>.json``:
    ``{"reader": "<module>.<function>", "args": {...}}``; the module is
    ``perfbench/readers/<module>.py``."""
    spec = _load(os.path.join(repo, "perfbench", "metrics", name + ".json"))
    mod, fn = spec["reader"].rsplit(".", 1)
    return getattr(_module_at(repo, "readers", mod), fn), \
        spec.get("args", {})
