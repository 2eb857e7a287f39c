"""What a run is, read from data files found by name.

``BENCHMARK.json`` at the checkout's root lists the cells. A cell names a
configuration (its ``file``, a JSON object of sizes), a traffic mix
(``bench/traffic/<traffic>.json``) and has its correctness limits in
``bench/limits/<cell>.json``. Each per-layer metric is a reader of its own,
``bench/metrics/<metric>.py``, with a ``read(ctx)`` function. A later cell or
metric is added by adding such files and entries; no code here names one.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Callable, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


@dataclasses.dataclass(frozen=True)
class Cell:
    """One entry of ``workloads`` with everything it names, loaded."""

    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    root: str

    @property
    def fit_iters(self) -> int:
        return int(self.traffic["fit_iters"])

    @property
    def record_every(self) -> int:
        return int(self.traffic["record_every"])


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = ROOT) -> Cell:
    """The cell `name` of ``<root>/BENCHMARK.json``; KeyError if absent."""
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {root}/BENCHMARK.json; "
                       f"have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _load_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = _load_json(
        os.path.join(root, "bench", "traffic", f"{w['traffic']}.json"))
    limits = _load_json(os.path.join(root, "bench", "limits", f"{name}.json"))
    return Cell(
        name=name, chips=int(w["chips"]), config=config, traffic=traffic,
        limits=limits,
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, name)],
        root=root)


def metric_reader(name: str, root: str = ROOT) -> Callable[[dict],
                                                           Optional[float]]:
    """``read(ctx)`` of ``<root>/bench/metrics/<name>.py``."""
    path = os.path.join(root, "bench", "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def sodda_config(config: dict):
    """The program's ``SoddaConfig`` for a configuration file."""
    from repro.configs.sodda_svm import SoddaConfig

    P, Q = int(config["P"]), int(config["Q"])
    N, M = int(config["N"]), int(config["M"])
    if N % P or M % (P * Q):
        raise ValueError(f"{config['name']}: N={N} must split over P={P} and "
                         f"M={M} over P*Q={P * Q}")
    return SoddaConfig(
        name=config["name"], loss=config["loss"], P=P, Q=Q, n=N // P,
        m=M // Q, L=int(config["L"]), b_frac=float(config["b_frac"]),
        c_frac=float(config["c_frac"]), d_frac=float(config["d_frac"]),
        lr0=float(config["lr0"]))


def read_metrics(cell: Cell, ctx: dict) -> Dict[str, dict]:
    """Each per-layer metric of `cell` that its reader finds, by name."""
    out = {}
    for m in cell.per_layer:
        value = metric_reader(m["name"], cell.root)(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out
