"""Find a cell's files by name: ``BENCHMARK.json`` names each cell's
configuration and traffic mix, and each per-layer metric; every one of
them is a file of its own under this package.

    configs/<config>.json    the deployment; its "driver" names the
                             module under drivers/ that runs it
    traffic/<traffic>.json   parameters of the traffic mix
    metrics/<metric>.py      a reader: read(record) -> number or None

A later change adds a cell, a mix, a configuration or a metric as new
files and entries, without editing a file that is already here.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import os
import re
from typing import Callable, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
#: the checkout root, which holds BENCHMARK.json and the program's src/
ROOT = os.path.dirname(BENCH_DIR)

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@dataclasses.dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with everything it resolves to."""

    name: str
    chips: int
    config: dict
    traffic: dict
    driver: Callable
    end_to_end: List[dict]
    per_layer: List[dict]


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _load_json(kind: str, name: str, bench_dir: str) -> dict:
    if not NAME_RE.match(name):
        raise ValueError(f"bad {kind} name {name!r}")
    with open(os.path.join(bench_dir, kind, f"{name}.json")) as f:
        return json.load(f)


def load_reader(metric: str, bench_dir: str = BENCH_DIR) -> Callable:
    """The ``read(record)`` function of ``metrics/<metric>.py``."""
    if not NAME_RE.match(metric):
        raise ValueError(f"bad metric name {metric!r}")
    path = os.path.join(bench_dir, "metrics", f"{metric}.py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + re.sub(r"\W", "_", metric), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_driver(name: str) -> Callable:
    """``drivers/<name>.py``'s ``run`` function."""
    if not re.match(r"^[a-z][a-z0-9_]*$", name):
        raise ValueError(f"bad driver name {name!r}")
    return importlib.import_module(f"bench.drivers.{name}").run


def reports(metric: dict, cell: str) -> bool:
    """Whether ``cell`` reports ``metric`` (all cells without a list)."""
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(bench: dict, cell: str, bench_dir: str = BENCH_DIR) -> Cell:
    """The cell named ``cell``, with its config, traffic, driver and the
    metrics it reports. Raises ``KeyError`` for an unknown cell."""
    by_name = {w["name"]: w for w in bench["workloads"]}
    w = by_name[cell]
    config = _load_json("configs", w["config"], bench_dir)
    traffic = _load_json("traffic", w["traffic"], bench_dir)
    return Cell(
        name=cell, chips=int(w["chips"]), config=config, traffic=traffic,
        driver=load_driver(config["driver"]),
        end_to_end=[m for m in bench["end_to_end"] if reports(m, cell)],
        per_layer=[m for m in bench["per_layer"] if reports(m, cell)])


def read_per_layer(cell: Cell, record: dict,
                   bench_dir: str = BENCH_DIR) -> Dict[str, dict]:
    """Every per-layer metric of ``cell`` that its reader finds in
    ``record``; a reader that finds nothing returns None and the metric
    is left out."""
    out: Dict[str, dict] = {}
    for m in cell.per_layer:
        value: Optional[float] = load_reader(m["name"], bench_dir)(record)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
