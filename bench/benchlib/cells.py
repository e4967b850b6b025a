"""Find everything a cell needs by the names in ``BENCHMARK.json``.

A cell (an entry of ``workloads``) names a configuration and a traffic mix.
Its files, all under ``bench/``:

* ``configs/<config>.json``: the configuration as it is run (the entry's
  ``file``), whose ``family`` names ``configs/<family>.py`` (the plain
  reference) and ``configs/<family>_program.py`` (how the program holds it);
* ``mixes/<traffic>.json``: the traffic's parameters;
* ``limits/<cell>.json``: the limits of the comparison that decides
  ``correct``;
* ``metrics/<metric>.py``: one reader per per-layer metric, with a
  ``read(run)`` function.

Adding a configuration, a mix or a metric therefore adds files and entries
and edits none.
"""

from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass
from types import ModuleType
from typing import Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: Dict
    traffic: str
    mix: Dict
    limits: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]
    bench_dir: str = BENCH_DIR

    def module(self, stem: str) -> ModuleType:
        return load_module(os.path.join(self.bench_dir, "configs", stem + ".py"))

    @property
    def family(self) -> str:
        return self.config["family"]


def load_module(path: str) -> ModuleType:
    name = "bench_" + os.path.splitext(os.path.basename(path))[0].replace(
        ".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: str = ROOT) -> Dict:
    return _json(os.path.join(root, "BENCHMARK.json"))


def _applies(metric: Dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def resolve(workload: str, root: str = ROOT, spec: Dict = None) -> Cell:
    spec = spec if spec is not None else load_benchmark(root)
    bench_dir = os.path.join(root, spec["paths"][0])
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; have {sorted(cells)}")
    w = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = dict(_json(os.path.join(root, conf["file"])), name=conf["name"])
    mix = _json(os.path.join(bench_dir, "mixes", w["traffic"] + ".json"))
    limits = _json(os.path.join(bench_dir, "limits", workload + ".json"))
    e2e = [m for m in spec["end_to_end"] if _applies(m, workload)]
    layer = [m for m in spec["per_layer"] if _applies(m, workload)]
    return Cell(name=workload, chips=int(w["chips"]), config_name=conf["name"],
                config=config, traffic=w["traffic"], mix=mix, limits=limits,
                end_to_end=e2e, per_layer=layer, bench_dir=bench_dir)


def metric_reader(cell: Cell, metric: str) -> ModuleType:
    return load_module(os.path.join(cell.bench_dir, "metrics", metric + ".py"))
