"""Finding a cell's pieces by name.

A cell ``<config>.<traffic>`` is made of ``configs/<config>.json``,
``traffic/<traffic>.json``, ``cells/<config>.<traffic>.json`` (the offered
rate, which depends on both) and, for each metric that ``BENCHMARK.json``
lists for it, ``metrics/<metric>.py``. The configuration file names, by path
under the benchmark's directory, the three modules of its architecture:
``weights`` (its binding to the program, its weights and adapters),
``reference`` (the plain forward and train step the check compares with) and
``counts`` (its model FLOPs and bytes). Nothing here knows a cell, a
configuration, an architecture or a metric by name: a new one is a new file
and a new ``BENCHMARK.json`` entry.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType, SimpleNamespace
from typing import Dict, List

ARCH_MODULES = ("weights", "reference", "counts")

BENCH_DIR = Path(__file__).resolve().parents[1]
REPO_DIR = BENCH_DIR.parent


class Bench:
    """The benchmark's data: ``root`` holds configs/, traffic/, cells/,
    metrics/ and peaks.json; ``benchmark`` is the parsed ``BENCHMARK.json``."""

    def __init__(self, root: Path = BENCH_DIR,
                 benchmark_json: Path = REPO_DIR / "BENCHMARK.json"):
        self.root = Path(root)
        self.benchmark = json.loads(Path(benchmark_json).read_text())
        self._modules: Dict[Path, ModuleType] = {}

    def cell(self, workload: str) -> Dict:
        for w in self.benchmark["workloads"]:
            if w["name"] == workload:
                return w
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")

    def config(self, name: str) -> Dict:
        return json.loads((self.root / "configs" / f"{name}.json").read_text())

    def traffic(self, name: str) -> Dict:
        return json.loads((self.root / "traffic" / f"{name}.json").read_text())

    def rate(self, workload: str) -> float:
        """Requests per second offered to this cell."""
        path = self.root / "cells" / f"{workload}.json"
        return float(json.loads(path.read_text())["rate_per_s"])

    def peaks(self, device_kind: str) -> Dict:
        table = json.loads((self.root / "peaks.json").read_text())
        if device_kind not in table:
            raise KeyError(f"device kind {device_kind!r} is not in "
                           f"peaks.json; known: "
                           f"{sorted(k for k in table if k != 'source')}")
        return table[device_kind]

    def metrics_for(self, workload: str, trace: bool) -> List[Dict]:
        """The metric entries a run of this cell reports: its end-to-end
        metrics, or with ``trace`` its per-layer ones."""
        group = self.benchmark["per_layer" if trace else "end_to_end"]
        return [m for m in group
                if "workloads" not in m or workload in m["workloads"]]

    def metric_module(self, name: str) -> ModuleType:
        return self.module(f"metrics/{name}.py")

    def arch(self, conf: Dict) -> SimpleNamespace:
        """The architecture modules a configuration file names."""
        return SimpleNamespace(**{k: self.module(conf[k])
                                  for k in ARCH_MODULES})

    def module(self, rel: str) -> ModuleType:
        """A module of the benchmark's, by its path under ``root``; loaded
        once per process."""
        path = (self.root / rel).resolve()
        if path not in self._modules:
            name = "bench_" + "".join(c if c.isalnum() else "_"
                                      for c in rel.removesuffix(".py"))
            spec = importlib.util.spec_from_file_location(name, path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            self._modules[path] = mod
        return self._modules[path]
