"""Readings of the program's own spans and of the forward's name scopes.

The span readers take a snapshot of the program's recorder
(``repro.obs.snapshot()``) and a window on the recorder's clock, with
times on any one origin. The scope readers take the compiled text of the
forward (:func:`op_scopes`) and a trace reduced by ``tracered.reduce``
(:func:`scope_seconds`). Every reader returns None where the run has
nothing for it to read.

The forward runs each layer under the name scope ``layerNN``. A part of
a layer is any name scope directly under ``layerNN`` that has a further
component after it (the last component of an ``op_name`` is the
primitive): ``.../layer03/im2col/jit(_pad)/pad`` is ``layer03/im2col``,
``.../layer07/add/add`` is ``layer07/add`` and ``.../layer03/reshape`` is
``layer03``. A transform's component (``jit(_pad)``) is no part.
"""
from __future__ import annotations

import collections
import re
from typing import Dict, List, Optional, Tuple

STEP = "serve.step"
WAIT = "serve.wait"
STEP_PHASES = ("serve.admit", "serve.h2d", "serve.dispatch", WAIT,
               "serve.d2h", "serve.record")
SETUP_SPANS = ("setup.pack", "serve.verify", "serve.warmup", "jax.trace",
               "jax.compile", "jax.cache_load")
_SCOPE = re.compile(r"(?:^|/)(layer\d\d)(?:/([^/()]+)(?=/))?")

Interval = Tuple[float, float]
Scopes = Dict[str, Tuple[Optional[str], Optional[str]]]


# -- the program's spans ---------------------------------------------------
def _dur(s: dict) -> float:
    return s["end_ns"] - s["start_ns"]


def _children(snapshot: dict) -> Dict[int, List[dict]]:
    kids: Dict[int, List[dict]] = collections.defaultdict(list)
    for s in snapshot["spans"]:
        kids[s["parent"]].append(s)
    return kids


def _steps(snapshot: dict, win: Interval) -> List[dict]:
    return [s for s in snapshot["spans"]
            if s["name"] == STEP and win[0] <= s["start_ns"] < win[1]]


def step_phases(snapshot: Optional[dict], win: Interval) -> Optional[dict]:
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


def host_ms_per_step(snapshot: Optional[dict], win: Interval
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


def images_in_window(snapshot: Optional[dict], win: Interval) -> int:
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
    no part is named; None outside every layer (see the module's
    docstring)."""
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


def op_scopes(hlo_text: str) -> Scopes:
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


def scope_seconds(reduction, scopes: Scopes) -> Dict[Optional[str], float]:
    """Device seconds in the window by scope (None: outside every layer),
    summed over the devices. ``reduction.devices[*].op_ns`` is keyed
    ``"<instruction> <shape>"``."""
    out: Dict[Optional[str], float] = collections.defaultdict(float)
    for d in reduction.devices.values():
        for name, ns in d.op_ns.items():
            out[scopes.get(name.split(" ", 1)[0], (None, None))[0]] += \
                ns / 1e9
    return dict(out)


def layers(scope_s: Optional[Dict[Optional[str], float]], images: int
           ) -> Optional[dict]:
    """Device ms per image answered for each ``layerNN``, split into every
    part found in any layer, and ``other`` for the layer's operations
    outside a part; and the share of the device time outside every layer
    (``unscoped_pct``)."""
    total = sum((scope_s or {}).values())
    if not total or images <= 0:
        return None
    parts = sorted({s.partition("/")[2] for s in scope_s if s} - {""})
    out: Dict[str, dict] = {}
    for scope, s in sorted(scope_s.items(), key=lambda kv: kv[0] or ""):
        if scope is None:
            continue
        layer, _, part = scope.partition("/")
        rec = out.setdefault(layer, dict.fromkeys(parts + ["other"], 0.0))
        rec[part or "other"] += 1e3 * s / images
    return {"per_layer_ms_per_img": out,
            "unscoped_pct": 100.0 * scope_s.get(None, 0.0) / total}


def im2col_ms_per_img(scope_s: Optional[Dict[Optional[str], float]],
                      images: int) -> Optional[float]:
    """Device time of the operations under an ``im2col`` part, summed over
    the chips, per image answered in the window."""
    secs = [s for k, s in (scope_s or {}).items()
            if k and k.endswith("/im2col")]
    if not secs or images <= 0:
        return None
    return 1e3 * sum(secs) / images
