"""Finds a cell's files by the names ``BENCHMARK.json`` gives: nothing
here lists cells, metrics, traffic kinds or services."""

from __future__ import annotations

import importlib.util
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_ROOT = os.path.dirname(BENCH_DIR)


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``benchmark/<kind>/<name>.py`` as a module (kind is ``services``,
    ``drivers``, ``layer_metrics`` or ``reference``)."""
    path = os.path.join(BENCH_DIR, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} file {path}")
    modname = f"benchmark.{kind}.{name}"
    if modname in sys.modules:
        return sys.modules[modname]
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One entry of ``workloads`` with its configuration, traffic mix
    and the metrics that list it (a metric without ``workloads`` is in
    every cell)."""

    def __init__(self, workload: str, rehearse: bool = False):
        bench = load_json(REPO_ROOT, "BENCHMARK.json")
        cells = {w["name"]: w for w in bench["workloads"]}
        if workload not in cells:
            raise KeyError(f"BENCHMARK.json has no workload {workload!r}")
        self.name = workload
        self.entry = cells[workload]
        self.chips = int(self.entry["chips"])
        conf = {c["name"]: c for c in bench["configs"]}[self.entry["config"]]
        self.config = load_json(REPO_ROOT, conf["file"])
        self.traffic = load_json(BENCH_DIR, "traffic",
                                 f"{self.entry['traffic']}.json")
        self.rehearse = rehearse
        self.sizes = dict(self.config["sizes"])
        if rehearse:
            # tiny sizes that only debug the command on the CPU
            self.sizes.update(self.config.get("rehearse_sizes", {}))
        self.end_to_end = [m for m in bench["end_to_end"]
                           if workload in m.get("workloads", [workload])]
        self.per_layer = [m for m in bench["per_layer"]
                          if workload in m.get("workloads", [workload])]
