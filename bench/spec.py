"""The benchmark as data: ``BENCHMARK.json`` and the files it names.

Every piece is found by name under the checkout root:

* a configuration ``c``      -> ``bench/configs/c.json``
* a traffic mix ``t``         -> ``bench/traffic/t.json``
* a metric ``m`` or ``m.x``   -> ``bench/metrics/m.x.py``, else
  ``bench/metrics/m.py`` (one reader may serve a quantity that is split
  by cell, such as ``device_idle_pct.fleet`` and ``.serve``)

A later change adds a cell, a mix or a metric by adding such files and
an entry in ``BENCHMARK.json``; nothing here names one.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path


class Spec:
    def __init__(self, root):
        self.root = Path(root)
        self.manifest = json.loads((self.root / "BENCHMARK.json").read_text())

    def cell(self, name: str) -> dict:
        for w in self.manifest["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{[w['name'] for w in self.manifest['workloads']]}")

    def config(self, name: str) -> dict:
        for c in self.manifest["configs"]:
            if c["name"] == name:
                return json.loads((self.root / c["file"]).read_text())
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        return json.loads(
            (self.root / "bench" / "traffic" / f"{name}.json").read_text())

    def metrics(self, cell: str, trace: bool) -> list:
        """The metric entries ``cell`` reports: its per-layer ones in a
        traced run, else its end-to-end ones."""
        group = self.manifest["per_layer" if trace else "end_to_end"]
        return [m for m in group if cell in m.get("workloads", [cell])]

    def reader(self, metric: str):
        """The ``read(ctx)`` function of ``metric``'s reader file."""
        d = self.root / "bench" / "metrics"
        path = d / f"{metric}.py"
        if not path.exists():
            path = d / f"{metric.split('.')[0]}.py"
        if not path.exists():
            raise FileNotFoundError(f"no reader for metric {metric!r}")
        spec = importlib.util.spec_from_file_location(
            "bench_metric_" + metric.replace(".", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read
