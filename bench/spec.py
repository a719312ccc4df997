"""What ``BENCHMARK.json`` says about one cell, and the files it names.

A cell names a configuration (``bench/configs/<config>.json``, whose
``file`` the configuration entry gives) and a traffic mix
(``bench/traffic/<traffic>.json``). Each metric is a reader of its own,
``bench/metrics/<name>.py``, with a function ``read(ctx)`` that returns a
number, or None where the run has nothing for it to read.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Callable, Dict, List

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


@dataclasses.dataclass
class Metric:
    name: str
    unit: str
    read: Callable


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[Metric]
    per_layer: List[Metric]


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(
        "bench_" + name.replace(".", "_").replace("/", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(name: str) -> Callable:
    return _module(os.path.join(BENCH, "metrics", name + ".py"), name).read


def reference(config: dict):
    """The configuration's plain reference module."""
    name = config["reference"]
    return _module(os.path.join(BENCH, "references", name + ".py"), name)


def _reports(entry: dict, cell: str, cell_e2e: set) -> bool:
    """A metric with ``workloads`` is reported in those cells; a per-layer
    metric without it in every cell that reports the metric it moves."""
    if "workloads" in entry:
        return cell in entry["workloads"]
    if "moves" in entry:
        return entry["moves"] in cell_e2e
    return True


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = load_json(os.path.join(root, "bench", "traffic",
                                     w["traffic"] + ".json"))
    e2e = [m for m in bench["end_to_end"] if _reports(m, name, set())]
    e2e_names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"] if _reports(m, name, e2e_names)]
    return Cell(name, int(w["chips"]), config, traffic,
                [Metric(m["name"], m["unit"], reader(m["name"])) for m in e2e],
                [Metric(m["name"], m["unit"], reader(m["name"]))
                 for m in layer])


def peaks(device_kind: str) -> Dict[str, float]:
    table = load_json(os.path.join(BENCH, "peaks.json"))["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json")
    return table[device_kind]
