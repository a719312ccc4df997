"""What ``BENCHMARK.json`` says about one cell, and the files it names.

A cell names a configuration (``bench/configs/<config>.json``, whose
``file`` the configuration entry gives) and a traffic mix
(``bench/traffic/<traffic>.json``). Each metric is a reader of its own,
``bench/metrics/<name>.py``, with a function ``read(ctx)`` that returns a
number, or None where the run has nothing for it to read.

A configuration's ``layers`` is a table of convs that may form a graph.
Each entry has ``kernel``, ``in_channels``, ``out_channels``, ``stride``,
``padding`` and ``pool_after``, and may have:

* ``name``: needed only on an entry that another entry refers to;
* ``input``: the name of the entry whose output this conv reads, or
  ``"image"``; by default the entry before it, or the image for the
  first;
* ``add``: the name of an entry whose output is added to this conv's
  output before its activation;
* ``relu``: whether the activation follows; by default true.

``padding`` is ``"SAME"``, ``"VALID"`` or explicit pairs
``[[top, bottom], [left, right]]``. ``pool_after`` is null or a max-pool
``[window, stride]`` or ``[window, stride, padding]``, its padding
``"VALID"`` (the default) or pairs; a pool pads with -inf. Maps are
square, so the pairs for H and W are equal. An entry's output is
conv, then the ``add``, then the ReLU, then the pool; other entries read
it after its pool. The answer is the last entry's output. References
point only to earlier entries, so every prefix of a table is a table.
:func:`layer_graph` reads a table and refuses one that breaks these
rules.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Callable, Dict, List, Optional, Tuple, Union

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


IMAGE = -1                     # what an entry reads when it reads the image
Pad = Union[str, Tuple[Tuple[int, int], Tuple[int, int]]]
_KEYS = {"kernel", "in_channels", "out_channels", "stride", "padding",
         "pool_after", "name", "input", "add", "relu"}


@dataclasses.dataclass(frozen=True)
class Layer:
    """One entry of a layer table, its references resolved to indices."""
    index: int
    name: Optional[str]
    kernel: int
    cin: int
    cout: int
    stride: int
    padding: Pad               # "SAME", "VALID" or ((top, bottom), (l, r))
    pool: Optional[Tuple[int, int, Pad]]   # (window, stride, padding)
    input: int                 # index of the entry read, or IMAGE
    add: Optional[int]         # index of the entry added, or None
    relu: bool

    @property
    def label(self) -> str:
        return f"entry {self.index}" + (f" ({self.name!r})" if self.name
                                        else "")


def _pad(value, where: str, allowed: Tuple[str, ...]) -> Pad:
    if isinstance(value, str):
        if value not in allowed:
            raise ValueError(f"{where}: padding {value!r} is not one of "
                             f"{allowed} or pairs")
        return value
    try:
        (t, b), (l, r) = value
        pads = ((int(t), int(b)), (int(l), int(r)))
    except (TypeError, ValueError):
        raise ValueError(f"{where}: padding {value!r} is neither a name nor "
                         f"[[top, bottom], [left, right]]") from None
    if pads[0] != pads[1] or min(pads[0]) < 0:
        raise ValueError(f"{where}: padding {value!r} is not the same "
                         f"non-negative pair for H and W (maps are square)")
    return pads


def layer_graph(config: dict) -> List[Layer]:
    """The configuration's layer table as resolved entries; raises
    ValueError for a table that breaks the rules in this module's
    docstring, naming the entry and the key."""
    out: List[Layer] = []
    names: Dict[str, int] = {}

    def ref(i: int, key: str, value) -> int:
        if value == "image" and key == "input":
            return IMAGE
        if value not in names:
            raise ValueError(f"layer table entry {i}: {key} {value!r} names "
                             f"no earlier entry")
        return names[value]

    for i, e in enumerate(config["layers"]):
        where = f"layer table entry {i}"
        unknown = set(e) - _KEYS
        if unknown:
            raise ValueError(f"{where}: unknown keys {sorted(unknown)}")
        src = ref(i, "input", e["input"]) if "input" in e else i - 1
        add = ref(i, "add", e["add"]) if e.get("add") is not None else None
        pool = e["pool_after"]
        if pool:
            if len(pool) not in (2, 3):
                raise ValueError(f"{where}: pool_after {pool!r} is not "
                                 f"[window, stride] or [window, stride, "
                                 f"padding]")
            pool = (int(pool[0]), int(pool[1]),
                    _pad(pool[2] if len(pool) == 3 else "VALID",
                         f"{where} pool_after", ("VALID",)))
        layer = Layer(i, e.get("name"), int(e["kernel"]),
                      int(e["in_channels"]), int(e["out_channels"]),
                      int(e["stride"]),
                      _pad(e["padding"], f"{where} padding",
                           ("SAME", "VALID")),
                      pool or None, src, add, bool(e.get("relu", True)))
        src_cout = (int(config["in_channels"]) if src == IMAGE
                    else out[src].cout)
        if layer.cin != src_cout:
            raise ValueError(f"{where}: in_channels {layer.cin} but its "
                             f"input has {src_cout} channels")
        if add is not None and out[add].cout != layer.cout:
            raise ValueError(f"{where}: add {e['add']!r} has "
                             f"{out[add].cout} channels, not {layer.cout}")
        if layer.name is not None:
            if layer.name == "image" or layer.name in names:
                raise ValueError(f"{where}: name {layer.name!r} is taken")
            names[layer.name] = i
        out.append(layer)
    return out
