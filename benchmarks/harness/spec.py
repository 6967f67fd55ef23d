"""Finds a cell's files by the names in ``BENCHMARK.json``.

``BENCHMARK.json`` names the command, the window, the metrics and the
cells. Whatever belongs to one configuration, one traffic mix, one cell or
one per-layer metric is a file of its own:

- ``<file of the configuration>``: as ``configs[].file`` gives it;
- ``<base>/traffic/<mix>.json`` and ``<base>/workloads/<cell>.json``,
  where ``<base>`` is the directory that holds the configuration's
  ``configs/`` directory;
- ``benchmarks/layer_metrics/<metric>.py`` (a reader with ``read(run)``)
  or ``<metric>.json`` (``{"same_as": "<other metric>"}``), looked for
  beside the cell's own ``<base>/layer_metrics/`` first.
"""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass
from typing import Callable, List, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_ROOT = os.path.dirname(BENCH_DIR)


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    chips: int
    run_seconds: int
    cfg: dict        # the configuration's file
    mix: dict        # the traffic mix's file
    cell: dict       # the cell's own file: limits of the output check
    end_to_end: List[dict]
    per_layer: List[dict]
    base: str


def _reported(metric: dict, cell: str, e2e_names: List[str]) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return "moves" not in metric or metric["moves"] in e2e_names


def load_cell(workload: str, benchmark_json: Optional[str] = None) -> Cell:
    path = benchmark_json or os.path.join(REPO_ROOT, "BENCHMARK.json")
    bench = _load_json(path)
    entry = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if entry is None:
        raise SystemExit(
            f"no workload {workload!r} in {path}; it has "
            f"{[w['name'] for w in bench['workloads']]}"
        )
    conf = next(c for c in bench["configs"] if c["name"] == entry["config"])
    cfg_path = os.path.join(REPO_ROOT, conf["file"])
    base = os.path.dirname(os.path.dirname(cfg_path))
    e2e = [
        m for m in bench["end_to_end"] if _reported(m, workload, [])
    ]
    names = [m["name"] for m in e2e]
    return Cell(
        name=workload,
        chips=int(entry["chips"]),
        run_seconds=int(bench["run_seconds"]),
        cfg=_load_json(cfg_path),
        mix=_load_json(os.path.join(base, "traffic", entry["traffic"] + ".json")),
        cell=_load_json(os.path.join(base, "workloads", workload + ".json")),
        end_to_end=e2e,
        per_layer=[
            m for m in bench["per_layer"] if _reported(m, workload, names)
        ],
        base=base,
    )


def _metric_file(metric: str, base: str) -> str:
    for d in (os.path.join(base, "layer_metrics"),
              os.path.join(BENCH_DIR, "layer_metrics")):
        for ext in (".py", ".json"):
            path = os.path.join(d, metric + ext)
            if os.path.exists(path):
                return path
    raise SystemExit(
        f"per-layer metric {metric!r} has no reader file under layer_metrics/"
    )


def load_reader(metric: str, base: str) -> Callable:
    """The ``read(run)`` of a per-layer metric's own file."""
    path = _metric_file(metric, base)
    if path.endswith(".json"):  # {"same_as": <a metric with a reader>}
        path = _metric_file(_load_json(path)["same_as"], base)
        if not path.endswith(".py"):
            raise SystemExit(f"same_as of {metric!r} names no reader")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + os.path.basename(path)[:-3].replace(".", "_").replace("-", "_"),
        path,
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
