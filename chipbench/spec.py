"""Find a cell's configuration, traffic mix and metric readers by name.

``BENCHMARK.json`` names them; each lives in a file of its own:

- ``chipbench/configs/<config>.json``
- ``chipbench/traffic/<traffic>.json``
- ``chipbench/metrics/<metric>.py``, which defines ``read(run)``

so a later cell or metric is added by adding files and entries only.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Callable, Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
BENCHMARK = os.path.join(REPO, "BENCHMARK.json")


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]  # the BENCHMARK.json entries this cell reports
    per_layer: List[dict]


def load_benchmark(path: str = BENCHMARK) -> dict:
    with open(path) as f:
        return json.load(f)


def _json(kind: str, name: str) -> dict:
    with open(os.path.join(HERE, kind, f"{name}.json")) as f:
        return json.load(f)


def reports(metric: dict, cell: str) -> bool:
    """Whether ``cell`` reports ``metric`` (no ``workloads``: all do)."""
    return cell in metric.get("workloads", [cell])


def resolve(bench: dict, cell_name: str) -> Cell:
    cells = {w["name"]: w for w in bench["workloads"]}
    if cell_name not in cells:
        raise KeyError(f"no workload {cell_name!r} in BENCHMARK.json")
    w = cells[cell_name]
    config = _json("configs", w["config"])
    if config["name"] != w["config"]:
        raise ValueError(f"configs/{w['config']}.json names "
                         f"{config['name']!r}")
    return Cell(
        name=cell_name,
        chips=int(w["chips"]),
        config=config,
        traffic=_json("traffic", w["traffic"]),
        end_to_end=[m for m in bench["end_to_end"] if reports(m, cell_name)],
        per_layer=[m for m in bench["per_layer"] if reports(m, cell_name)],
    )


def reader(metric_name: str) -> Callable:
    """``read(run) -> float | None`` of ``metrics/<metric_name>.py``."""
    path = os.path.join(HERE, "metrics", f"{metric_name}.py")
    spec = importlib.util.spec_from_file_location(
        f"chipbench.metrics.{metric_name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks(device_kind: str) -> Dict[str, float]:
    """The peak table's row for ``device_kind``; a kind missing from the
    table is an error, never a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "chipbench/peaks.json")
    return table[device_kind]
