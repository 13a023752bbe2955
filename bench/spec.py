"""``BENCHMARK.json`` and the files it names.

Everything that belongs to one configuration, traffic mix or metric is a
file of its own, found by its name:

* ``bench/configs/<config>.json``   the deployment's sizes and limits;
* ``bench/traffic/<traffic>.json``  the traffic's parameters;
* ``bench/metrics/<metric>.py``     a reader: ``read(ctx)`` returns the
  metric's value, or None where the run holds nothing to read.
"""

from __future__ import annotations

import importlib.util
import json
import re
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Dict, List

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@dataclass
class Cell:
    name: str
    chips: int
    config: dict           # the configuration file's contents
    traffic: dict          # the traffic file's contents
    end_to_end: List[dict]
    per_layer: List[dict]


def load_spec() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def config_path(spec: dict, name: str) -> Path:
    entry = next(c for c in spec["configs"] if c["name"] == name)
    return ROOT / entry["file"]


def traffic_path(name: str) -> Path:
    return BENCH / "traffic" / f"{name}.json"


def metric_path(name: str) -> Path:
    return BENCH / "metrics" / f"{name}.py"


def reports(metric: dict, cell: str, e2e_names: List[str]) -> bool:
    """Whether ``metric`` is reported in ``cell``: where it lists its cells,
    in those; otherwise in every cell (a per-layer metric: in every cell
    that reports the end-to-end metric it moves)."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return "moves" not in metric or metric["moves"] in e2e_names


def cell(spec: dict, workload: str) -> Cell:
    entries = [w for w in spec["workloads"] if w["name"] == workload]
    if not entries:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; known: "
                       f"{[w['name'] for w in spec['workloads']]}")
    w = entries[0]
    e2e = [m for m in spec["end_to_end"] if reports(m, workload, [])]
    names = [m["name"] for m in e2e]
    layer = [m for m in spec["per_layer"] if reports(m, workload, names)]
    return Cell(name=workload, chips=int(w["chips"]),
                config=json.loads(config_path(spec, w["config"]).read_text()),
                traffic=json.loads(traffic_path(w["traffic"]).read_text()),
                end_to_end=e2e, per_layer=layer)


def reader(name: str) -> Callable:
    """The ``read`` function of ``bench/metrics/<name>.py``."""
    path = metric_path(name)
    mod_spec = importlib.util.spec_from_file_location(
        "bench_metric_" + re.sub(r"\W", "_", name), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def read_metrics(metrics: List[dict], ctx) -> Dict[str, dict]:
    """{name: {"value", "unit"}} of every metric whose reader found
    something to read."""
    out = {}
    for m in metrics:
        value = reader(m["name"])(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
