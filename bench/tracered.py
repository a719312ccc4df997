"""Reduction of a profiler trace to device busy time, time per device
operation and idle gaps.

A trace is first made compact (:func:`compact`): planes, their lines and
their events as ``[name, start_ns, duration_ns]``, nothing else. The rest
works on that form, so the tests can hold a recorded trace as JSON.

On a TPU each chip is a plane named ``/device:TPU:<id>``, and its line
``XLA Ops`` holds one event per HLO operation, named by the HLO
instruction's text (``%_worklist_spmm_pallas.14 = f32[401408,128]{...}
custom-call(...)`` for a walker kernel). :func:`op_name` cuts that to the
instruction and its result shape (``_worklist_spmm_pallas.14
f32[401408,128]``). Its line ``Async XLA Ops`` holds copies that overlap
the ops and is not counted as busy. Event times count from the profile's
start (``profile_start_ns``, wall clock). Host planes are named
``/host:...``; the harness adds its own spans as ``/host:bench``, among
them ``bench.window``, which marks the measured window.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")

Interval = Tuple[float, float]


def compact(xplane_path: str) -> dict:
    """Read an ``.xplane.pb`` into ``{"planes": [{"name", "lines": [{"name",
    "events": [[name, start_ns, duration_ns], ...]}]}]}``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(xplane_path)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            events = [[e.name, float(e.start_ns), float(e.duration_ns)]
                      for e in line.events]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    out = {"planes": planes}
    for plane in data.planes:
        for key, value in plane.stats:
            if key == "profile_start_time":   # the events' time origin
                out["profile_start_ns"] = int(value)
    return out


def find_xplane(log_dir: str) -> str:
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {len(found)}")
    return found[0]


def window(trace: dict, span: str = WINDOW_SPAN) -> Interval:
    """[start, end] of the host span that marks the measured window."""
    for plane in trace["planes"]:
        if not plane["name"].startswith("/host:"):
            continue
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                if name == span:
                    return start, start + dur
    raise ValueError(f"no host span {span!r} in the trace")


def op_name(text: str) -> str:
    """``%name = f32[..]{layout} op(...)`` -> ``name f32[..]``; other names
    are kept as they are."""
    if not text.startswith("%") or " = " not in text:
        return text
    name, rest = text[1:].split(" = ", 1)
    shape = rest.split("{", 1)[0].split(" ", 1)[0]
    return f"{name} {shape}"


def device_ops(trace: dict, device_ids: Iterable[int]
               ) -> Dict[int, List[Tuple[str, float, float]]]:
    """Per device id: its operations as (name, start, end), in start order.
    Takes the ``XLA Ops`` line, or every line of the plane if it has none."""
    want = set(device_ids)
    out: Dict[int, List[Tuple[str, float, float]]] = {}
    for plane in trace["planes"]:
        m = _DEVICE.match(plane["name"])
        if not m or int(m.group(1)) not in want:
            continue
        lines = [l for l in plane["lines"] if l["name"] == OPS_LINE] \
            or plane["lines"]
        ops = [(op_name(n), s, s + d) for l in lines
               for n, s, d in l["events"]]
        out[int(m.group(1))] = sorted(ops, key=lambda o: o[1])
    return out


def union(intervals: Iterable[Interval]) -> List[Interval]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def clip(intervals: Iterable[Interval], win: Interval) -> List[Interval]:
    lo, hi = win
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(busy: Sequence[Interval], win: Interval) -> List[Interval]:
    """Idle intervals of the window between merged busy intervals."""
    out, t = [], win[0]
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < win[1]:
        out.append((t, win[1]))
    return out


@dataclasses.dataclass
class DeviceTime:
    """One device over the window, in nanoseconds."""
    busy_ns: float
    op_ns: Dict[str, float]       # time per operation name, clipped to it
    started: Dict[str, List[float]]   # name -> [count, whole ns] of the
                                      # operations that start in the window
    idle: List[Interval]          # idle gaps


@dataclasses.dataclass
class Reduction:
    window_ns: float
    devices: Dict[int, DeviceTime]
    host_spans: List[Tuple[str, float, float]]

    @property
    def busy_s(self) -> float:
        """Busy seconds, averaged over the devices."""
        return sum(d.busy_ns for d in self.devices.values()) \
            / len(self.devices) / 1e9

    @property
    def window_s(self) -> float:
        return self.window_ns / 1e9

    def op_seconds(self, match) -> float:
        """Seconds of the operations whose name ``match`` accepts, summed
        over the devices."""
        return sum(ns for d in self.devices.values()
                   for name, ns in d.op_ns.items() if match(name)) / 1e9

    def started(self, match) -> Tuple[int, float]:
        """Count and whole seconds of the operations whose name ``match``
        accepts and that start in the window, summed over the devices."""
        n, ns = 0, 0.0
        for d in self.devices.values():
            for name, (c, t) in d.started.items():
                if match(name):
                    n, ns = n + int(c), ns + t
        return n, ns / 1e9

    def top_ops(self, n: int = 10) -> List[List]:
        """The ``n`` operations that took most time, seconds averaged over
        the devices."""
        tot: Dict[str, float] = {}
        for d in self.devices.values():
            for name, ns in d.op_ns.items():
                tot[name] = tot.get(name, 0.0) + ns
        k = len(self.devices)
        return [[name, ns / k / 1e9] for name, ns in
                sorted(tot.items(), key=lambda kv: -kv[1])[:n]]

    def longest_gaps(self, n: int = 10) -> List[List]:
        """The ``n`` longest idle gaps of any device, each named by the
        innermost host span that covers its middle."""
        all_gaps = sorted((g for d in self.devices.values() for g in d.idle),
                          key=lambda g: g[0] - g[1])[:n]
        return [[self.host_activity((s + e) / 2), (e - s) / 1e9]
                for s, e in all_gaps]

    def host_activity(self, t: float) -> str:
        best: Optional[Tuple[float, str]] = None
        for name, s, e in self.host_spans:
            if s <= t <= e and (best is None or e - s < best[0]):
                best = (e - s, name)
        return f"host: {best[1]}" if best else "host: no span"


def reduce(trace: dict, device_ids: Sequence[int],
           span: str = WINDOW_SPAN) -> Reduction:
    """Busy time, time per operation and idle gaps of ``device_ids`` over
    the window that the host span ``span`` marks."""
    win = window(trace, span)
    ops = device_ops(trace, device_ids)
    missing = set(device_ids) - set(ops)
    if missing:
        raise ValueError(f"no trace plane for devices {sorted(missing)}")
    devices = {}
    for dev, dev_ops in ops.items():
        op_ns: Dict[str, float] = {}
        started: Dict[str, List[float]] = {}
        spans = []
        for name, s, e in dev_ops:
            c = clip([(s, e)], win)
            if c:
                op_ns[name] = op_ns.get(name, 0.0) + c[0][1] - c[0][0]
                spans.append(c[0])
            if win[0] <= s < win[1]:
                rec = started.setdefault(name, [0, 0.0])
                rec[0] += 1
                rec[1] += e - s
        busy = union(spans)
        devices[dev] = DeviceTime(sum(e - s for s, e in busy), op_ns,
                                  started, gaps(busy, win))
    host = [(n, s, s + d) for p in trace["planes"]
            if p["name"].startswith("/host:")
            for l in p["lines"] for n, s, d in l["events"]
            if s < win[1] and s + d > win[0] and n != span]
    return Reduction(win[1] - win[0], devices, host)

