"""Cells of ``BENCHMARK.json`` and the files that belong to them, found by
name: ``configs/<config>.json``, ``traffic/<traffic>.json``,
``metrics/<metric>.py`` and ``systems/<system>.py`` (the system a
configuration names). Adding a cell, configuration, traffic mix or metric
means adding files and entries; no file here changes.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import os
from typing import Callable, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict
    traffic: dict
    end_to_end: List[dict]      # the cell's end-to-end metrics, setup_s too
    per_layer: List[dict]       # the cell's per-layer metrics
    bench_dir: str = BENCH_DIR


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: str = ROOT,
              bench_dir: str = BENCH_DIR) -> Cell:
    """The cell ``name`` with its configuration and traffic files read."""
    bench = _read_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    config = _read_json(os.path.join(bench_dir, "configs",
                                     w["config"] + ".json"))
    traffic = _read_json(os.path.join(bench_dir, "traffic",
                                      w["traffic"] + ".json"))
    return Cell(name=name, chips=int(w["chips"]), config_name=w["config"],
                traffic_name=w["traffic"], config=config, traffic=traffic,
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _applies(m, name)],
                bench_dir=bench_dir)


def load_system(name: str):
    """``systems/<name>.py``: set-up, window, check and end-to-end metrics
    of one kind of deployment."""
    return importlib.import_module(f"systems.{name}")


def load_reader(metric: str, bench_dir: str = BENCH_DIR) -> Callable:
    """``metrics/<metric>.py``'s ``read(run) -> float | None``."""
    path = os.path.join(bench_dir, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + metric.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_per_layer(cell: Cell, run: dict) -> Dict[str, dict]:
    """Every per-layer metric of the cell that finds something to read."""
    out = {}
    for m in cell.per_layer:
        value: Optional[float] = load_reader(m["name"], cell.bench_dir)(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
