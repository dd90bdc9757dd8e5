"""BENCHMARK.json and the files it names, found by name.

* a configuration ``<name>`` is the JSON file its entry names, and its
  ``family`` is ``bench/families/<family>.py`` (weights, reference,
  counts);
* a traffic mix ``<name>`` is ``bench/traffic/<name>.json``;
* a metric ``<name>`` is ``bench/metrics/<name>.py``, whose ``read(rec)``
  returns its number from the run's record, or None where it finds
  nothing to read.

A later change adds a configuration, mix or metric by adding its file
and its entry; nothing here changes.
"""

from __future__ import annotations

import importlib.util
import json
import os
from typing import Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))


def _module(path: str, name: str):
    if not os.path.isfile(path):
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Benchmark:
    def __init__(self, root: str, bench_dir: str = HERE):
        self.root = root
        self.dir = bench_dir
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            self.data = json.load(f)

    def cell(self, name: str) -> Dict:
        for w in self.data["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{[w['name'] for w in self.data['workloads']]}")

    def config(self, name: str) -> Dict:
        for c in self.data["configs"]:
            if c["name"] == name:
                with open(os.path.join(self.root, c["file"])) as f:
                    return json.load(f)
        raise KeyError(f"no configuration {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> Dict:
        with open(os.path.join(self.dir, "traffic", name + ".json")) as f:
            return json.load(f)

    def family(self, name: str):
        return _module(os.path.join(self.dir, "families", name + ".py"),
                       "bench_family_" + name)

    def metrics(self, cell: str, kind: str) -> List[Dict]:
        """The ``end_to_end`` or ``per_layer`` entries this cell reports."""
        return [m for m in self.data[kind]
                if "workloads" not in m or cell in m["workloads"]]

    def reader(self, metric: str, metrics_dir: Optional[str] = None):
        d = metrics_dir or os.path.join(self.dir, "metrics")
        return _module(os.path.join(d, metric + ".py"),
                       "bench_metric_" + metric.replace(".", "_")).read
