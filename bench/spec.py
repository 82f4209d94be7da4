"""Find a cell's configuration, traffic mix and metric readers by name.

``BENCHMARK.json`` names them; each lives in a file of its own, so a new
configuration, traffic mix or metric is a new file and never an edit.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Callable

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict          # the configuration file, with its "name"
    traffic: dict         # the traffic file, with its "name"
    end_to_end: tuple     # BENCHMARK.json entries this cell reports
    per_layer: tuple

    @property
    def batch(self) -> int:
        """LPs per call: the traffic's, else the configuration's."""
        return int(self.traffic.get("batch") or self.config["batch"])


def load_benchmark() -> dict:
    with open(ROOT / "BENCHMARK.json") as f:
        return json.load(f)


def _reports(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", (cell,))


def load_cell(name: str) -> Cell:
    spec = load_benchmark()
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[w["config"]]
    with open(ROOT / cfg_entry["file"]) as f:
        config = {"name": w["config"], **json.load(f)}
    with open(BENCH / "traffic" / f"{w['traffic']}.json") as f:
        traffic = {"name": w["traffic"], **json.load(f)}
    e2e = tuple(m for m in spec["end_to_end"] if _reports(m, name))
    reported = {m["name"] for m in e2e}
    per_layer = tuple(m for m in spec["per_layer"]
                      if name in m.get("workloads", ())
                      or ("workloads" not in m and m["moves"] in reported))
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=per_layer)


def metric_reader(name: str) -> Callable:
    """``read`` of ``metrics/<name>.py``: takes a ``measure.Run`` and
    returns the metric's value, or None where it finds nothing to read."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_')}", path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(f"metric {name!r} has no reader at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
