"""Finding a cell's parts by name: ``BENCHMARK.json`` names the cells and
metrics; each configuration, traffic mix and metric reader is a file of its
own under ``perfbench/``.  Nothing here knows any particular one."""
from __future__ import annotations

import importlib
import importlib.util
import json
import os
import re
from types import ModuleType
from typing import Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))
_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _safe(name: str) -> str:
    if not _NAME.match(name):
        raise ValueError(f"bad name {name!r}")
    return name


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


class Bench:
    """``BENCHMARK.json`` at ``root`` and the files it names."""

    def __init__(self, root: str):
        self.root = root
        self.doc = load_json(os.path.join(root, "BENCHMARK.json"))
        self.pkg = os.path.join(root, "perfbench")

    def workload(self, name: str) -> dict:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        for c in self.doc["configs"]:
            if c["name"] == name:
                cfg = load_json(os.path.join(self.root, c["file"]))
                if cfg["name"] != name:
                    raise ValueError(f"{c['file']} holds {cfg['name']!r}, "
                                     f"not {name!r}")
                return cfg
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        return load_json(os.path.join(self.pkg, "traffic",
                                      _safe(name) + ".json"))

    def metrics(self, workload: str, trace: bool) -> List[dict]:
        """The metrics a run of ``workload`` reports: the end-to-end ones
        with ``trace`` off, the per-layer ones with it on."""
        key = "per_layer" if trace else "end_to_end"
        return [m for m in self.doc[key]
                if "workloads" not in m or workload in m["workloads"]]

    def reader(self, metric: str) -> ModuleType:
        return load_module(os.path.join(self.pkg, "metrics",
                                        _safe(metric) + ".py"),
                           "perfbench_metric_" + metric.replace(".", "_")
                           .replace("-", "_"))


def load_module(path: str, modname: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(modname, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def model_module(cfg: dict) -> ModuleType:
    """Glue to the program for the configuration's model family."""
    return importlib.import_module("perfbench.models." + _safe(cfg["model"]))


def reference_module(cfg: dict) -> ModuleType:
    return importlib.import_module("perfbench.references."
                                   + _safe(cfg["model"]))


def read_metrics(bench: Bench, workload: str, trace: bool,
                 run: dict) -> Dict[str, dict]:
    """Each metric's reader over the run record; a reader that finds
    nothing to read returns None and the metric is left out."""
    out = {}
    for m in bench.metrics(workload, trace):
        value = bench.reader(m["name"]).read(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out
