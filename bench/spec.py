"""Find the parts of a cell by name.

``BENCHMARK.json`` names the cells; everything that belongs to one
configuration, traffic mix or metric sits in a file of its own under the
benchmark's directory, found by the name it is given there:

  <root>/BENCHMARK.json             the cells, metrics and bounds
  <file of the config entry>        one deployment (``bench/configs``)
  bench/traffic/<mix>.json          one traffic mix
  bench/end_to_end/<metric>.py      reader of one end-to-end metric
  bench/layer_metrics/<metric>.py   reader of one per-layer metric

A reader is a module with ``read(run)``: it returns the metric's value, or
None where the run has nothing for it to read (``bench.record.RunRecord``
says what a run holds).
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: list        # BENCHMARK.json metric entries reported here
    per_layer: list


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    return Cell(
        name=name, chips=int(w["chips"]), config_name=w["config"],
        config=load_json(root / configs[w["config"]]["file"]),
        traffic_name=w["traffic"],
        traffic=load_json(root / "bench" / "traffic" / f"{w['traffic']}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, name)])


def load_reader(kind: str, name: str, root: Path = ROOT):
    """The ``read`` function of ``bench/<kind>/<name>.py``."""
    path = root / "bench" / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_metrics(entries: list, kind: str, run, root: Path = ROOT) -> dict:
    """``{name: {"value", "unit"}}`` for each metric entry whose reader
    found something to read."""
    out = {}
    for m in entries:
        value = load_reader(kind, m["name"], root)(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
