"""Find a cell and everything it names, by name, from BENCHMARK.json.

A cell (``workloads`` entry) names a configuration and a traffic mix.
Each lives in a file of its own under ``bench/``:

* ``configs/<config>.json``   the configuration as it is run;
* ``traffic/<traffic>.json``  the job and its parameters; ``"job"``
  names ``jobs/<job>.py``, which runs the system under test;
* ``limits/<workload>.json``  the limits of the numbers that decide
  ``correct``;
* ``metrics/<metric>.py``     the reader of one per-layer metric.

Nothing here knows a cell, a configuration or a metric by name, so a
later cell adds files and entries and edits none.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Dict, List

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: Dict
    traffic_name: str
    traffic: Dict
    limits: Dict
    end_to_end: List[Dict]   # entries of BENCHMARK.json this cell reports
    per_layer: List[Dict]


def _load_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: Dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(workload: str, root: str = ROOT) -> Cell:
    """The cell ``workload`` of ``<root>/BENCHMARK.json`` with its files.
    Raises KeyError for a name BENCHMARK.json does not hold."""
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_entry = configs[w["config"]]
    config = _load_json(os.path.join(root, cfg_entry["file"]))
    traffic = _load_json(os.path.join(BENCH, "traffic", w["traffic"] + ".json"))
    limits = _load_json(os.path.join(BENCH, "limits", workload + ".json"))
    e2e = [m for m in bench["end_to_end"] if _applies(m, workload)]
    layer = [m for m in bench["per_layer"] if _applies(m, workload)]
    return Cell(workload, int(w["chips"]), w["config"], config, w["traffic"],
                traffic, limits, e2e, layer)


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = os.path.join(BENCH, kind, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
