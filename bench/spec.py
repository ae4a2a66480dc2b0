"""Everything a run needs, found by name under the benchmark's root.

`BENCHMARK.json` (at the repository root) names cells, configurations
and metrics; each lives in a file of its own:

  bench/configs/<config>.json      sizes, source, program mapping
  bench/traffic/<traffic>.json     traffic parameters and pool sizes
  bench/limits/<cell>.json         the limit of each number compared
  bench/metrics/<metric>.py        a reader: read(rec) -> value or None
  bench/reference/<family>.py      the plain float32 forward

Adding a cell, a traffic mix or a metric is adding files and entries.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Callable, Dict, List

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


@dataclasses.dataclass
class Cell:
    name: str
    root: str
    config: Dict
    traffic: Dict
    limits: Dict[str, float]
    chips: int
    end_to_end: List[Dict]
    per_layer: List[Dict]

    def reader(self, metric: str) -> Callable:
        return load_module(os.path.join(self.root, "bench", "metrics",
                                        metric + ".py"),
                           "bench_metric_" + metric).read

    def reference(self):
        return load_module(os.path.join(self.root, "bench", "reference",
                                        self.config["reference"] + ".py"),
                           "bench_reference_" + self.config["reference"])


def load_module(path: str, name: str):
    sp = importlib.util.spec_from_file_location(name.replace(".", "_"),
                                                path)
    mod = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(mod)
    return mod


def _read(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = ROOT) -> Cell:
    """The cell `name` of `<root>/BENCHMARK.json` with its files."""
    spec = _read(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = _read(os.path.join(root, configs[w["config"]]["file"]))
    traffic = _read(os.path.join(root, "bench", "traffic",
                                 w["traffic"] + ".json"))
    limits = _read(os.path.join(root, "bench", "limits", name + ".json"))
    return Cell(name=name, root=root, config=config, traffic=traffic,
                limits=limits, chips=int(w["chips"]),
                end_to_end=[m for m in spec["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in spec["per_layer"]
                           if _applies(m, name)])
