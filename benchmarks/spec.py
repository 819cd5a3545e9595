"""Reading ``BENCHMARK.json`` and the data files it names. Everything that
belongs to one configuration, one traffic mix or one per-layer metric is a
file of its own, found by the name in ``BENCHMARK.json`` — a later PR adds a
cell by adding files and entries, editing nothing that is here."""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Any, Callable, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
REPO_ROOT = os.path.dirname(HERE)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    config: Dict[str, Any]     # benchmarks/configs/<config>.json
    traffic_name: str
    traffic: Dict[str, Any]    # benchmarks/traffic/<traffic>.json
    end_to_end: List[Dict[str, Any]]   # the metrics this cell reports
    per_layer: List[Dict[str, Any]]
    root: str                  # directory that holds BENCHMARK.json

    def for_rehearsal(self) -> "Cell":
        """The cell as the CPU rehearsal runs it: tiny traffic and engine sizes
        (the configuration's tiny preset is the family module's business)."""
        return dataclasses.replace(self, traffic=rehearsal_view(self.traffic))


def _load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def _in_cell(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(workload: str, benchmark_json: Optional[str] = None) -> Cell:
    path = benchmark_json or os.path.join(REPO_ROOT, "BENCHMARK.json")
    root = os.path.dirname(os.path.abspath(path))
    bench = _load_json(path)
    rows = [w for w in bench["workloads"] if w["name"] == workload]
    if not rows:
        raise SystemExit(
            f"unknown workload {workload!r}; BENCHMARK.json has "
            f"{[w['name'] for w in bench['workloads']]}"
        )
    row = rows[0]
    config_row = next(c for c in bench["configs"] if c["name"] == row["config"])
    end_to_end = [m for m in bench["end_to_end"] if _in_cell(m, workload)]
    reported = {m["name"] for m in end_to_end}
    return Cell(
        name=workload, chips=int(row["chips"]),
        config_name=row["config"],
        config=_load_json(os.path.join(root, config_row["file"])),
        traffic_name=row["traffic"],
        traffic=_load_json(os.path.join(root, "benchmarks", "traffic", row["traffic"] + ".json")),
        end_to_end=end_to_end,
        # a per-layer metric is reported only where the metric it moves is
        per_layer=[
            m for m in bench["per_layer"]
            if _in_cell(m, workload) and m["moves"] in reported
        ],
        root=root,
    )


def rehearsal_view(data: Dict[str, Any]) -> Dict[str, Any]:
    """A data file as the CPU rehearsal reads it: the keys under
    ``"rehearsal"`` replace the top-level ones (one level deep for dicts)."""
    out = {k: v for k, v in data.items() if k != "rehearsal"}
    for k, v in data.get("rehearsal", {}).items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = {**out[k], **v}
        else:
            out[k] = v
    return out


def _load_module(kind: str, name: str, root: Optional[str] = None):
    """``<root>/benchmarks/<kind>/<name>.py`` (``root`` is where the
    BENCHMARK.json in use lives), else the same file beside this module."""
    here = os.path.join(HERE, kind, name + ".py")
    path = os.path.join(root, "benchmarks", kind, name + ".py") if root else here
    if not os.path.exists(path):
        path = here
    spec = importlib.util.spec_from_file_location(f"benchmarks.{kind}.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_metric(kind: str, name: str, root: Optional[str] = None) -> Callable:
    """``read(result)`` of ``end_to_end/<name>.py`` or
    ``layer_metrics/<name>.py``. A reader that finds nothing to read returns
    ``None`` and the metric is left out of the line."""
    return _load_module(kind, name, root).read


def load_family(name: str):
    """``benchmarks/families/<name>.py``: how a configuration file becomes
    the program's model classes, and which plain reference checks it."""
    return _load_module("families", name)
