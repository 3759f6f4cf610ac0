"""Finds a cell's pieces from files alone.

`BENCHMARK.json` at the checkout's root names the cells, configurations
and metrics. Everything that belongs to one of them sits in a file of
its own under the benchmark's directory, found by the name:

  configs/<config>.json      the configuration as it is run; its
                             `model.name` names the model file
  models/<model>.py          one model: `weights(key, m)`,
                             `shards(key, n_clients, traffic, m)`,
                             `program_loss(m)`, `reference_loss(params,
                             batch)` and `train_flops_per_sample(m)`,
                             `m` the configuration's `model` group
  traffic/<traffic>.json     a traffic mix: parameters for a driver
  drivers/<driver>.py        the code that drives one kind of traffic
  metrics/<metric>.py        the reader of one per-layer metric
  limits/<workload>.json     the limit of each number `correct` compares

so adding a cell, a configuration, a model or a metric adds files and
entries and edits nothing that is there. A new model brings
`models/<model>.py`, `configs/<config>.json`, `traffic/<traffic>.json`
and `limits/<cell>.json`; a new metric brings `metrics/<metric>.py`;
only a new kind of traffic brings a driver.
"""
from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path
from types import ModuleType
from typing import Dict, List

BENCH_DIR = Path(__file__).resolve().parents[1]
CHECKOUT = BENCH_DIR.parents[1]


class RegistryError(Exception):
    pass


def load_module(path: Path, prefix: str) -> ModuleType:
    """Import the Python file at `path` under a private module name."""
    if not path.is_file():
        raise RegistryError(f"no file {path}")
    name = prefix + re.sub(r"\W", "_", path.stem)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Registry:
    """A view of one checkout's benchmark. `root` holds BENCHMARK.json,
    `bench_dir` the configs/, models/, traffic/, drivers/ and metrics/
    files."""

    def __init__(self, root: Path = CHECKOUT, bench_dir: Path = BENCH_DIR):
        self.root, self.bench_dir = Path(root), Path(bench_dir)
        path = self.root / "BENCHMARK.json"
        if not path.is_file():
            raise RegistryError(f"no {path}")
        self.spec = json.loads(path.read_text())

    def workload(self, name: str) -> Dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise RegistryError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> Dict:
        for c in self.spec["configs"]:
            if c["name"] == name:
                cfg = json.loads((self.root / c["file"]).read_text())
                cfg.setdefault("name", name)
                return cfg
        raise RegistryError(f"no config {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> Dict:
        path = self.bench_dir / "traffic" / f"{name}.json"
        if not path.is_file():
            raise RegistryError(f"no traffic file {path}")
        return json.loads(path.read_text())

    def driver(self, name: str) -> ModuleType:
        return load_module(self.bench_dir / "drivers" / f"{name}.py",
                           "chipbench_driver_")

    def model(self, name: str) -> ModuleType:
        return load_module(self.bench_dir / "models" / f"{name}.py",
                           "chipbench_model_")

    def metrics_of(self, workload: str, kind: str) -> List[Dict]:
        """The `end_to_end` or `per_layer` metrics this cell reports."""
        return [m for m in self.spec[kind]
                if workload in m.get("workloads", [workload])]

    def reader(self, metric: str) -> ModuleType:
        return load_module(self.bench_dir / "metrics" / f"{metric}.py",
                           "chipbench_metric_")

    def limits(self, workload: str) -> Dict:
        path = self.bench_dir / "limits" / f"{workload}.json"
        if not path.is_file():
            raise RegistryError(f"no limits file {path}")
        return json.loads(path.read_text())["limits"]
