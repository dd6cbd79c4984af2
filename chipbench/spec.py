"""Resolve a cell of `BENCHMARK.json` by name into what a run needs: its
configuration file, its traffic file and the readers of its metrics.

Everything is found by name, so a later change adds a configuration, a
traffic mix or a metric as new files plus new entries in `BENCHMARK.json`
and edits no file that is already here:

    configs/<config>.json    sizes, the deployment, the correctness limits
    traffic/<traffic>.json   parameters of one of the generators in
                             `traffic.py`, chosen by its "kind"
    metrics/<metric>.py      a `read(run)` function returning a number, or
                             None where the run has nothing to read
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    read: object          # read(run) -> float | None


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: tuple     # Metric, ...
    per_layer: tuple      # Metric, ...


def load_benchmark(path: str | None = None) -> dict:
    with open(path or os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _load_json(kind: str, name: str, base: str) -> dict:
    if not NAME.match(name):
        raise ValueError(f"bad {kind} name {name!r}")
    path = os.path.join(base, kind, f"{name}.json")
    with open(path) as f:
        return json.load(f)


def load_reader(name: str, base: str = HERE):
    """The `read` function of metrics/<name>.py."""
    if not NAME.match(name):
        raise ValueError(f"bad metric name {name!r}")
    path = os.path.join(base, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    if spec is None:
        raise FileNotFoundError(path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def _applies(entry: dict, cell: str) -> bool:
    cells = entry.get("workloads")
    return cells is None or cell in cells


def resolve(workload: str, bench: dict | None = None,
            base: str = HERE) -> Cell:
    bench = load_benchmark() if bench is None else bench
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(known: {sorted(cells)})")
    w = cells[workload]
    config = _load_json("configs", w["config"], base)
    traffic = _load_json("traffic", w["traffic"], base)

    def metrics(entries):
        return tuple(Metric(name=m["name"], unit=m["unit"],
                            read=load_reader(m["name"], base))
                     for m in entries if _applies(m, workload))

    return Cell(name=workload, chips=int(w["chips"]), config=config,
                traffic=traffic,
                end_to_end=metrics(bench["end_to_end"]),
                per_layer=metrics(bench["per_layer"]))
