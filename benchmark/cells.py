"""Finds everything that belongs to a cell by the names in
`BENCHMARK.json`: `configs/<config>.json`, `traffic/<traffic>.json`,
`workloads/<cell>.json`, `families/<family>.py` and every
`layer_metrics/*.py`.  A later PR adds files and entries and edits none."""
from __future__ import annotations

import importlib.util
import json
import os
import re

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class Registry:
    """`root` holds `BENCHMARK.json` and `benchmark/`; `extra` directories
    (the tests' tiny sizes) are searched first and may hold a
    `workloads.json` with further cells."""

    def __init__(self, root: str | None = None, extra=()):
        self.root = root or repo_root()
        self.dirs = [*extra, os.path.join(self.root, "benchmark")]
        with open(os.path.join(self.root, "BENCHMARK.json")) as f:
            self.spec = json.load(f)
        self.workloads = {w["name"]: w for w in self.spec["workloads"]}
        for d in extra:
            more = os.path.join(d, "workloads.json")
            if os.path.exists(more):
                with open(more) as f:
                    self.workloads.update(
                        {w["name"]: w for w in json.load(f)})

    def find(self, kind: str, name: str, ext: str) -> str:
        if not NAME_RE.match(name):
            raise ValueError(f"{kind} name {name!r} is not a name")
        for d in self.dirs:
            path = os.path.join(d, kind, name + ext)
            if os.path.exists(path):
                return path
        raise FileNotFoundError(
            f"no {kind}/{name}{ext} under {self.dirs}")

    def json(self, kind: str, name: str) -> dict:
        with open(self.find(kind, name, ".json")) as f:
            return json.load(f)

    def cell(self, name: str) -> dict:
        if name not in self.workloads:
            raise KeyError(f"no workload {name!r}; there are "
                           f"{sorted(self.workloads)}")
        entry = self.workloads[name]
        config = self.json("configs", entry["config"])
        return {
            "name": name, "chips": int(entry["chips"]),
            "config_name": entry["config"],
            "traffic_name": entry["traffic"],
            "config": config,
            "traffic": self.json("traffic", entry["traffic"]),
            "workload": self.json("workloads", name),
            "family": _load_module(
                self.find("families", config["family"], ".py"),
                "benchmark_family_" + config["family"]),
        }

    def layer_metrics(self) -> list:
        found = {}
        for d in reversed(self.dirs):
            folder = os.path.join(d, "layer_metrics")
            if not os.path.isdir(folder):
                continue
            for fname in sorted(os.listdir(folder)):
                if fname.endswith(".py") and not fname.startswith("_"):
                    module = _load_module(
                        os.path.join(folder, fname),
                        "benchmark_layer_metric_" + fname[:-3])
                    found[module.NAME] = module
        return [found[k] for k in sorted(found)]
