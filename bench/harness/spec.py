"""``BENCHMARK.json`` and the files it names, found by name.

A cell (an entry of ``workloads``) joins one configuration file
(``configs[].file``), one traffic mix (``bench/traffic/<traffic>.json``,
whose ``kind`` names a generator ``bench/traffic/<kind>.py``) and the
metrics that apply to it: the end-to-end metrics without a ``workloads``
list or with the cell in it, and with ``--trace 1`` the per-layer metrics
likewise, each read by ``bench/metrics/<name>.py``.  Adding a cell, a mix
or a metric is adding files and entries; nothing here names one.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from types import ModuleType
from typing import Any, Dict, List


def load_module(path: str) -> ModuleType:
    """Import one file of the benchmark by its path."""
    name = "bench_" + os.path.relpath(path).replace(os.sep, "_")[:-3]
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One workload with everything it names, loaded."""
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    generator: ModuleType
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    readers: Dict[str, ModuleType]


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_cell(root: str, workload: str) -> Cell:
    """The cell ``workload`` of ``<root>/BENCHMARK.json``."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"BENCHMARK.json has {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    with open(os.path.join(root, configs[w["config"]]["file"])) as f:
        config = json.load(f)
    traffic_dir = os.path.join(root, "bench", "traffic")
    with open(os.path.join(traffic_dir, w["traffic"] + ".json")) as f:
        traffic = json.load(f)
    generator = load_module(os.path.join(traffic_dir, traffic["kind"] + ".py"))
    e2e = [m for m in bench["end_to_end"] if _applies(m, workload)]
    e2e_names = {m["name"] for m in bench["end_to_end"]
                 if _applies(m, workload)}
    # a per-layer metric without a list applies wherever its end-to-end
    # metric is reported
    per_layer = [m for m in bench["per_layer"]
                 if workload in m.get("workloads", [workload])
                 and ("workloads" in m or m["moves"] in e2e_names)]
    metrics_dir = os.path.join(root, "bench", "metrics")
    readers = {m["name"]: load_module(os.path.join(metrics_dir,
                                                   m["name"] + ".py"))
               for m in per_layer}
    return Cell(name=workload, chips=int(w["chips"]), config=config,
                traffic=traffic, generator=generator, end_to_end=e2e,
                per_layer=per_layer, readers=readers)
