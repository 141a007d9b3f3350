"""Find a cell's files by the names in ``BENCHMARK.json``.

A cell is a workload entry: a configuration, a traffic mix and a chip
count.  Its files are found by name, so a later cell, configuration, mix or
metric is new files plus an entry, with no edit to the harness:

* ``bench/configs/<config>.json``   the configuration as it is run;
* ``bench/traffic/<traffic>.json``  the mix, read by the module of its
  ``kind``, ``bench/harness/<kind>.py`` (``harness.train``);
* ``bench/limits/<workload>.json``  the limit of each compared number;
* ``bench/metrics/<metric>.py``     a per-layer metric's reader, a module
  with ``read(ctx) -> float | None``.
"""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass
from typing import Callable


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list[dict]      # this cell's entries, in file order
    per_layer: list[dict]


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, cell: str, reported: set[str]) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves") in reported if "moves" in metric else True


def load_cell(root: str, workload: str, bench_dir: str | None = None) -> Cell:
    """The cell ``workload`` of ``<root>/BENCHMARK.json``; its files are
    looked up under ``bench_dir`` (default ``<root>/bench``)."""
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    bench_dir = bench_dir or os.path.join(root, "bench")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: "
                       f"{sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _load_json(os.path.join(root, configs[w["config"]]["file"]))
    e2e = [m for m in bench["end_to_end"] if _applies(m, workload, set())]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _applies(m, workload, reported)]
    return Cell(
        name=workload, chips=int(w["chips"]), config=config,
        traffic=_load_json(os.path.join(bench_dir, "traffic",
                                        f"{w['traffic']}.json")),
        limits=_load_json(os.path.join(bench_dir, "limits",
                                       f"{workload}.json")),
        end_to_end=e2e, per_layer=per_layer)


def metric_reader(name: str, bench_dir: str) -> Callable[[dict], float | None]:
    """``read`` of ``<bench_dir>/metrics/<name>.py`` (names may hold dots,
    so the module is loaded by path, not imported by name)."""
    path = os.path.join(bench_dir, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
