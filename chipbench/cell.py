"""Find a cell's files by the names in BENCHMARK.json.

A cell is one entry of `workloads`: {name, config, traffic, chips, why}.
Its configuration is `configs/<config>.json` (the path BENCHMARK.json gives
under `configs[].file`), its traffic mix `traffic/<traffic>.json`, its
metrics the files under `end_to_end/` and `layer_metrics/` that
BENCHMARK.json lists for it. Nothing here imports JAX.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Any, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class BenchError(RuntimeError):
    """The run cannot produce a result line (exit code 1, no last line)."""


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """`chipbench/<kind>/<name>.py`, found by name: a new reader, runner or
    reference is a new file."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.isfile(path):
        raise BenchError(f"no {kind[:-1]} named {name!r}: {path} is missing")
    spec = importlib.util.spec_from_file_location(
        f"chipbench.{kind}.{name.replace('.', '_').replace('-', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@dataclasses.dataclass
class Metric:
    name: str
    unit: str
    better: str
    source: str
    spec: Dict[str, Any]          # the metric's own file: reader + params
    moves: Optional[str] = None
    layer: Optional[str] = None


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    why: str
    config_name: str
    config: Dict[str, Any]
    traffic_name: str
    traffic: Dict[str, Any]
    end_to_end: List[Metric]
    per_layer: List[Metric]
    rehearsal: bool = False

    @property
    def runner(self) -> str:
        return self.config["runner"]


def _metric_applies(entry: Dict[str, Any], cell_name: str) -> bool:
    cells = entry.get("workloads")
    return cells is None or cell_name in cells


def _load_metric(entry: Dict[str, Any], end_to_end: bool) -> Metric:
    sub = "end_to_end" if end_to_end else "layer_metrics"
    path = os.path.join(HERE, sub, entry["name"] + ".json")
    if not os.path.isfile(path):
        raise BenchError(f"metric {entry['name']!r} has no file {path}")
    return Metric(name=entry["name"], unit=entry["unit"],
                  better=entry["better"], source=entry["source"],
                  spec=load_json(path),
                  moves=entry.get("moves"), layer=entry.get("layer"))


def load_benchmark(root: str = ROOT) -> Dict[str, Any]:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def load_cell(workload: str, root: str = ROOT) -> Cell:
    """The cell `workload` of BENCHMARK.json; failing that, a rehearsal
    cell of `chipbench/rehearsal.json` (tiny sizes, in no benchmark cell,
    never allowed to print a result line off the TPU)."""
    bench = load_benchmark(root)
    rehearsal = False
    entry = next((w for w in bench["workloads"] if w["name"] == workload),
                 None)
    configs = {c["name"]: c["file"] for c in bench["configs"]}
    if entry is None:
        extra = load_json(os.path.join(HERE, "rehearsal.json"))
        entry = next((w for w in extra["workloads"]
                      if w["name"] == workload), None)
        if entry is None:
            names = [w["name"] for w in bench["workloads"]
                     + extra["workloads"]]
            raise BenchError(f"no workload {workload!r}; have {names}")
        configs.update({c["name"]: c["file"] for c in extra["configs"]})
        rehearsal = True
    like = entry.get("metrics_of", entry["name"])
    return Cell(
        name=entry["name"], chips=int(entry["chips"]), why=entry["why"],
        config_name=entry["config"],
        config=load_json(os.path.join(root, configs[entry["config"]])),
        traffic_name=entry["traffic"],
        traffic=load_json(os.path.join(HERE, "traffic",
                                       entry["traffic"] + ".json")),
        end_to_end=[_load_metric(m, True) for m in bench["end_to_end"]
                    if _metric_applies(m, like)],
        per_layer=[_load_metric(m, False) for m in bench["per_layer"]
                   if _metric_applies(m, like)],
        rehearsal=rehearsal)


def load_peaks(device_kind: str) -> Dict[str, Any]:
    peaks = load_json(os.path.join(HERE, "peaks.json"))
    if device_kind not in peaks:
        raise BenchError(
            f"device kind {device_kind!r} is not in chipbench/peaks.json "
            f"({sorted(peaks)}): add it with its source, there is no "
            f"default")
    return peaks[device_kind]
