#!/usr/bin/env python3
"""The program's own spans and the forward's per-layer name scopes, read
from runs of a cell with the program's recorder (``repro.obs``) on.

    python3 bench/program_trace.py --workload <cell> --seconds 20 \
        --seeds <n> [<n> ...] [--trace 0|1] [--obs 0|1] \
        [--keep-trace FILE] [--out FILE]

``--trace 1`` (the default) is ``run.py --trace 1`` (``harness.run``)
with the recorder on from set-up on, and prints per seed one JSON line:
the step's phases (``serve.*``), the set-up's parts, device time per
layer and part, the idle gaps named by the innermost host span (the
program's included), and the readings of ``host_ms_per_step``,
``im2col_ms_per_img``, ``pack_s``, ``warmup_s`` and ``compile_s``. ``--trace 0`` is ``run.py --trace 0``
with the recorder forced on (``--obs 1``) or left off: the recorder's
cost. Needs the cell's chips.

The functions below read a snapshot of the recorder
(``obs.snapshot()``), with times on any one origin, and a trace reduced
by ``tracered.reduce``; they return None where the run has nothing for
them to read.
"""
import time

T_START = time.monotonic()

import collections  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import sys  # noqa: E402
from typing import Dict, List, Optional, Tuple  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

STEP = "serve.step"
WAIT = "serve.wait"
STEP_PHASES = ("serve.admit", "serve.h2d", "serve.dispatch", WAIT,
               "serve.d2h", "serve.record")
SETUP_SPANS = ("setup.pack", "serve.verify", "serve.warmup", "jax.trace",
               "jax.compile", "jax.cache_load")
PARTS = ("walker", "im2col", "pool")
_SCOPE = re.compile(r"(?:^|/)(layer\d\d)(?:/(" + "|".join(PARTS) + r"))?")

Window = Tuple[float, float]


# -- the program's spans ---------------------------------------------------
def _dur(s: dict) -> float:
    return s["end_ns"] - s["start_ns"]


def _children(snapshot: dict) -> Dict[int, List[dict]]:
    kids: Dict[int, List[dict]] = collections.defaultdict(list)
    for s in snapshot["spans"]:
        kids[s["parent"]].append(s)
    return kids


def _steps(snapshot: dict, win: Window) -> List[dict]:
    return [s for s in snapshot["spans"]
            if s["name"] == STEP and win[0] <= s["start_ns"] < win[1]]


def step_phases(snapshot: Optional[dict], win: Window) -> Optional[dict]:
    """Mean and longest ms of ``serve.step`` and of each of its phases,
    over the steps that start in the window."""
    steps = _steps(snapshot, win) if snapshot else []
    if not steps:
        return None
    kids = _children(snapshot)
    per = collections.defaultdict(list)
    for st in steps:
        per[STEP].append(_dur(st))
        for c in kids[st["seq"]]:
            per[c["name"]].append(_dur(c))
    return {name: {"mean_ms": sum(v) / len(v) / 1e6, "max_ms": max(v) / 1e6,
                   "n": len(v)}
            for name, v in per.items()}


def host_ms_per_step(snapshot: Optional[dict], win: Window
                     ) -> Optional[float]:
    """Mean over the steps that start in the window of the step less its
    ``serve.wait``: the host's share of a step."""
    steps = _steps(snapshot, win) if snapshot else []
    if not steps:
        return None
    kids = _children(snapshot)
    host = [_dur(st) - sum(_dur(c) for c in kids[st["seq"]]
                           if c["name"] == WAIT) for st in steps]
    return sum(host) / len(host) / 1e6


def images_in_window(snapshot: Optional[dict], win: Window) -> int:
    """Requests answered by the steps that start and end in the window
    (the ``images`` of each ``serve.step``)."""
    return sum(s["attrs"].get("images", 0)
               for s in (snapshot or {}).get("spans", ())
               if s["name"] == STEP and win[0] <= s["start_ns"]
               and s["end_ns"] <= win[1])


def _before(snapshot: Optional[dict], name: str, t: float) -> List[dict]:
    return [s for s in (snapshot or {}).get("spans", ())
            if s["name"] == name and s["end_ns"] <= t]


def setup_phases(snapshot: Optional[dict], window_start: float
                 ) -> Optional[dict]:
    """Seconds per set-up span before the window (the spans of one name
    summed; ``jax.*`` spans nest in the others)."""
    if not snapshot:
        return None
    out = {n: sum(_dur(s) for s in _before(snapshot, n, window_start)) / 1e9
           for n in SETUP_SPANS}
    out["compile_proper"] = compile_s(snapshot, window_start)
    return out


def pack_s(snapshot: Optional[dict], window_start: float
           ) -> Optional[float]:
    """The offline packing chain's time (``setup.pack``)."""
    spans = _before(snapshot, "setup.pack", window_start)
    return sum(_dur(s) for s in spans) / 1e9 if spans else None


def warmup_s(snapshot: Optional[dict], window_start: float
             ) -> Optional[float]:
    """The server's warm-up of its buckets (``serve.warmup``)."""
    spans = _before(snapshot, "serve.warmup", window_start)
    return sum(_dur(s) for s in spans) / 1e9 if spans else None


def compile_s(snapshot: Optional[dict], window_start: float
              ) -> Optional[float]:
    """Compile proper before the window: the ``jax.compile`` spans less
    their ``jax.cache_load`` children (near 0 when every program came
    from the persistent cache). None without a ``jax.compile`` span
    before the window: the forward's compile is always one, a load from
    the cache included, so its absence means the spans were lost."""
    spans = _before(snapshot, "jax.compile", window_start)
    if not spans:
        return None
    kids = _children(snapshot)
    return sum(_dur(s) - sum(_dur(c) for c in kids[s["seq"]]
                             if c["name"] == "jax.cache_load")
               for s in spans) / 1e9


# -- the forward's name scopes on the device ---------------------------------
def scope_of(op_name: Optional[str]) -> Optional[str]:
    """``.../layer03/im2col/...`` -> ``layer03/im2col``; ``layer03`` where
    no part is named; None outside every layer."""
    m = _SCOPE.search(op_name or "")
    if not m:
        return None
    return m.group(1) + (f"/{m.group(2)}" if m.group(2) else "")


_INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (.*)$")
_OPCODE = re.compile(r"\s*[\w\-]+\(")


def _closing(text: str, i: int) -> int:
    """The index just past the parenthesis that closes ``text[i]``."""
    depth = 0
    for j in range(i, len(text)):
        if text[j] == "(":
            depth += 1
        elif text[j] == ")":
            depth -= 1
            if depth == 0:
                return j + 1
    return len(text)


def _operands(rest: str) -> List[str]:
    """The operand names of ``<shape> <opcode>(<operands>), <attrs>``. The
    shape may be a tuple, and its layouts hold parentheses
    (``{1,0:T(8,128)}``), so the operands are the parenthesis after the
    opcode."""
    end = _closing(rest, 0) if rest.startswith("(") else rest.find(" ")
    m = _OPCODE.match(rest, end) if end >= 0 else None
    if m is None:
        return []
    return re.findall(r"%([\w.\-]+)", rest[m.end() - 1:_closing(
        rest, m.end() - 1)])


def op_scopes(hlo_text: str) -> Dict[str, Tuple[Optional[str],
                                                 Optional[str]]]:
    """HLO instruction name -> ``(scope, origin)`` for the entry
    computation of a compiled program's text. The scope is that of the
    instruction's own ``op_name`` metadata (origin ``"own"``), else the
    first one inside the computation it calls (``"called"``), else, where
    the compiler dropped the metadata of the operations it rewrote
    (layout copies, prefetches), that of the first scoped operation that
    consumes its result (``"consumer"``); ``(None, None)`` where none
    of these holds one."""
    comps: Dict[str, List[dict]] = {}
    entry, cur = None, None
    for line in hlo_text.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            words = line.split()
            if words[0] == "ENTRY":
                entry = cur = words[1].lstrip("%")
            else:
                cur = words[0].lstrip("%")
            comps[cur] = []
            continue
        m = _INSTR.match(line)
        if m is None or cur is None:
            continue
        name, rest = m.groups()
        meta = re.search(r'op_name="([^"]*)"', rest)
        comps[cur].append({
            "name": name, "scope": scope_of(meta.group(1) if meta else None),
            "calls": re.findall(r"(?:calls|to_apply)=%?([\w.\-]+)", rest),
            "operands": _operands(rest)})
    if entry is None:
        return {}

    def inner(comp: str, seen: set) -> Optional[str]:
        for ins in comps.get(comp, ()):
            if ins["scope"]:
                return ins["scope"]
            for c in ins["calls"]:
                if c not in seen:
                    seen.add(c)
                    s = inner(c, seen)
                    if s:
                        return s
        return None

    own, users = {}, collections.defaultdict(list)
    for ins in comps[entry]:
        called = next((s for s in (inner(c, {c}) for c in ins["calls"])
                       if s), None)
        own[ins["name"]] = (ins["scope"], "own") if ins["scope"] else \
            (called, "called") if called else (None, None)
        for o in ins["operands"]:
            users[o].append(ins["name"])
    out = {}
    for name, found in own.items():
        queue, seen = collections.deque([name]), {name}
        while found[0] is None and queue:
            for u in users[queue.popleft()]:
                if own.get(u, (None,))[0]:
                    found = (own[u][0], "consumer")
                    break
                if u not in seen:
                    seen.add(u)
                    queue.append(u)
        out[name] = found
    return out


def _scope_seconds(reduction, scopes, key: int) -> Dict[Optional[str], float]:
    """Device seconds in the window by scope (``key`` 0) or by origin
    (``key`` 1), summed over the devices. ``reduction.devices[*].op_ns``
    is keyed ``"<instruction> <shape>"``."""
    out: Dict[Optional[str], float] = collections.defaultdict(float)
    for d in reduction.devices.values():
        for name, ns in d.op_ns.items():
            out[scopes.get(name.split(" ", 1)[0], (None, None))[key]] += \
                ns / 1e9
    return dict(out)


def layers(reduction, scopes, images: int) -> Optional[dict]:
    """Device ms per image answered for each ``layerNN``, split into
    walker, im2col, pool and other; the share of the operations' device
    time outside every layer (``unscoped_pct``); and the shares whose
    scope came from the operation's own metadata, from the computation it
    calls, or from a consumer (``scope_origin_pct``)."""
    if reduction is None or not scopes or images <= 0:
        return None
    secs = _scope_seconds(reduction, scopes, 0)
    total = sum(secs.values())
    if not total:
        return None
    out: Dict[str, dict] = {}
    for scope, s in sorted(secs.items(), key=lambda kv: kv[0] or ""):
        if scope is None:
            continue
        layer, _, part = scope.partition("/")
        rec = out.setdefault(layer, {p: 0.0 for p in PARTS + ("other",)})
        rec[part or "other"] += 1e3 * s / images
    origins = _scope_seconds(reduction, scopes, 1)
    return {"per_layer_ms_per_img": out,
            "unscoped_pct": 100.0 * secs.get(None, 0.0) / total,
            "scope_origin_pct": {o: 100.0 * origins.get(o, 0.0) / total
                                 for o in ("own", "called", "consumer")}}


def im2col_ms_per_img(reduction, scopes, images: int) -> Optional[float]:
    """Device time of the operations under an ``im2col`` scope, summed
    over the chips, per image answered in the window."""
    if reduction is None or not scopes or images <= 0:
        return None
    secs = _scope_seconds(reduction, scopes, 0)
    return 1e3 * sum(s for k, s in secs.items()
                     if k and k.endswith("/im2col")) / images


# -- runs --------------------------------------------------------------------
def forward_hlo(cell, seed: int) -> str:
    """The compiled text of the forward that ``harness.make_server``
    serves for the cell and seed. The model is packed again from the
    seed; its compile comes from the persistent cache that the run filled."""
    import jax
    import jax.numpy as jnp

    import harness
    import weights as W
    from repro.kernels.ops import on_tpu
    from repro.vision import compile_forward, data_mesh
    config = cell.config
    model = harness.build_model(config, W.make_weights(config, seed),
                                cell.chips)
    fwd = compile_forward(model, donate=on_tpu(), mesh=data_mesh(
        cell.chips) if cell.chips > 1 else None)
    size = int(config["input_size"])
    x = jax.ShapeDtypeStruct((int(cell.traffic["slots"]), size, size,
                              model.layers[0].conv.cin), jnp.float32)
    return fwd.lower(x).compile().as_text()


def traced_run(cell, seed: int, seconds: float, *, t_start: float,
               keep_trace: Optional[str] = None) -> dict:
    """``run.py --trace 1`` (``harness.run``) with the recorder on from
    set-up on; then the program's plane added to the device trace, and
    the forward's op scopes read from its compiled text. ``keep_trace``
    saves the trace, the snapshot and that text, gzipped JSON."""
    import gzip
    import shutil
    import tempfile

    import harness
    import tracered
    from repro import obs

    tmp = tempfile.mkdtemp(prefix="program_trace_")
    try:
        obs.enable()
        try:
            out = harness.run(cell, seed, seconds, True, t_start=t_start,
                              keep_trace=os.path.join(tmp, "raw.json.gz"))
        finally:
            obs.disable()
        with gzip.open(os.path.join(tmp, "raw.json.gz"), "rt") as f:
            raw = json.load(f)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    snap = obs.snapshot()
    origin = raw["profile_start_ns"]
    raw["planes"].append(obs.plane(origin))
    devices = harness.require_chips(cell.chips)
    red = tracered.reduce(raw, [d.id for d in devices])
    w0, w1 = tracered.window(raw)
    win = (origin + w0, origin + w1)
    images = images_in_window(snap, win)
    t_hlo = time.monotonic()
    hlo = forward_hlo(cell, seed)
    t_hlo = time.monotonic() - t_hlo
    scopes = op_scopes(hlo)
    if keep_trace:
        with gzip.open(keep_trace, "wt") as f:
            json.dump({"trace": raw, "program": snap, "hlo": hlo,
                       "window_ns": list(win), "images": images}, f)
    return {
        "workload": cell.name, "seed": seed, "correct": out["correct"],
        "img_per_s": images / seconds, "forward_text_s": t_hlo,
        "readings": {
            "host_ms_per_step": host_ms_per_step(snap, win),
            "im2col_ms_per_img": im2col_ms_per_img(red, scopes, images),
            "pack_s": pack_s(snap, win[0]),
            "warmup_s": warmup_s(snap, win[0]),
            "compile_s": compile_s(snap, win[0])},
        "metrics": {k: v["value"] for k, v in out["metrics"].items()},
        "step_phases": step_phases(snap, win),
        "setup_phases": setup_phases(snap, win[0]),
        "layers": layers(red, scopes, images),
        "device_idle_pct": 100.0 * (1 - red.busy_s / red.window_s),
        "idle_gaps": red.longest_gaps(),
        "compiles_in_window": out["compiles_in_window"],
        "window_diag": out["window_diag"], "device": out["device"]}


def untraced_run(cell, seed: int, seconds: float, *, t_start: float,
                 recorder: bool) -> dict:
    """``run.py --trace 0`` with the recorder on or off."""
    import harness
    from repro import obs
    if recorder:
        obs.enable()
    try:
        out = harness.run(cell, seed, seconds, False, t_start=t_start)
    finally:
        obs.disable()
    row = {"workload": cell.name, "seed": seed, "recorder": recorder,
           "correct": out["correct"],
           "metrics": {k: v["value"] for k, v in out["metrics"].items()},
           "window_diag": out["window_diag"]}
    if recorder:
        snap = obs.snapshot()
        row["spans"] = len(snap["spans"])
        row["step_phases"] = step_phases(snap, (0, float("inf")))
    return row


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--obs", type=int, choices=(0, 1), default=1)
    ap.add_argument("--keep-trace", default=None, metavar="FILE",
                    help="the first seed's compact trace, recorder "
                    "snapshot and compiled forward, gzipped JSON")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    import harness
    import spec
    cell = spec.load_cell(args.workload)
    t = T_START
    for i, seed in enumerate(args.seeds):
        try:
            if args.trace:
                row = traced_run(cell, seed, args.seconds, t_start=t,
                                 keep_trace=args.keep_trace if i == 0
                                 else None)
            else:
                row = untraced_run(cell, seed, args.seconds, t_start=t,
                                   recorder=bool(args.obs))
        except harness.NoChip as e:
            print(f"program_trace: {e}", file=sys.stderr)
            return 2
        line = json.dumps(row)
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")
        t = time.monotonic()
    return 0


if __name__ == "__main__":
    sys.exit(main())
