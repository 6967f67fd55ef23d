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
  beside the cell's own ``<base>/layer_metrics/`` first;
- ``families/<family>.py`` and ``references/<reference>.py``, by the two
  names in the configuration's file, looked for in the same two places:
  whatever the benchmark knows of an architecture (its weights, the
  program's configuration object, the algorithm's counts; its plain
  reference) is behind those two names.
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


def _find(kind: str, name: str, base: str, exts=(".py",)) -> Optional[str]:
    """``<kind>/<name><ext>``, under the cell's own ``<base>`` first."""
    for d in (os.path.join(base, kind), os.path.join(BENCH_DIR, kind)):
        for ext in exts:
            path = os.path.join(d, name + ext)
            if os.path.exists(path):
                return path
    return None


def _load_module(path: str, prefix: str):
    spec = importlib.util.spec_from_file_location(
        prefix + os.path.basename(path)[:-3].replace(".", "_").replace("-", "_"),
        path,
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _metric_file(metric: str, base: str) -> str:
    path = _find("layer_metrics", metric, base, (".py", ".json"))
    if path is None:
        raise SystemExit(
            f"per-layer metric {metric!r} has no reader file under layer_metrics/"
        )
    return path


def load_reader(metric: str, base: str) -> Callable:
    """The ``read(run)`` of a per-layer metric's own file."""
    path = _metric_file(metric, base)
    if path.endswith(".json"):  # {"same_as": <a metric with a reader>}
        path = _metric_file(_load_json(path)["same_as"], base)
        if not path.endswith(".py"):
            raise SystemExit(f"same_as of {metric!r} names no reader")
    return _load_module(path, "bench_metric_").read


# what the harness calls of a family's file, and nothing else of it
FAMILY_PROVIDES = (
    "make_weights", "model_config", "decode_step_work",
    "decode_token_flops", "prefill_flops",
)


def _load_named(cfg: dict, key: str, kind: str, base: str):
    """The module ``<kind>/<cfg[key]>.py``."""
    name = cfg.get(key)
    if not name:
        raise SystemExit(
            f"configuration {cfg.get('name')!r} names no `{key}` "
            f"(a file under {kind}/)"
        )
    path = _find(kind, name, base)
    if path is None:
        raise SystemExit(f"{key} {name!r} has no file under {kind}/")
    return _load_module(path, f"bench_{key}_")


def load_family(cfg: dict, base: str):
    """The module a configuration's ``family`` names. It provides
    ``make_weights(cfg, seed)`` (the pytree the program serves, in the
    served type, made on the device), ``model_config(cfg)`` (the program's
    own configuration object; raises where the program would derive a size
    other than the one the file states) and the algorithm's work from shapes
    and lengths alone: ``decode_step_work(cfg, contexts) -> (flops, bytes)``
    for one decode step over live sequences of those context lengths,
    ``decode_token_flops(cfg, context)`` and ``prefill_flops(cfg,
    prompt_len)``."""
    mod = _load_named(cfg, "family", "families", base)
    missing = [n for n in FAMILY_PROVIDES if not callable(getattr(mod, n, None))]
    if missing:
        raise SystemExit(f"{mod.__file__} does not provide {missing}")
    return mod


def load_reference(cfg: dict, base: str):
    """The plain reference a configuration's ``reference`` names."""
    return _load_named(cfg, "reference", "references", base)
