"""One run of one cell: set-up, the measured window, the check, the result.

Set-up builds the served model from the seed (weights on the device,
packed by the program's offline chain), makes a ``VisionServer`` with the
one compiled shape of the configuration's input size and warms it up, and
makes the image pool. The window then starts on a fresh ``WallClock`` and
drives the server through its public ``submit``/``step`` for ``seconds``:
a closed loop of ``clients`` callers. Answers are taken out of
``server.produced`` as they arrive; a seeded sample of them is kept for
the check. Requests still open at the window's end are served to the end
(their images do not count as answered in the window). With ``trace`` the
profiler records the window.
"""
from __future__ import annotations

import contextlib
import dataclasses
import os
import shutil
import sys
import tempfile
import time
from typing import Dict, List, Optional

import numpy as np

import check
import loadgen
import spec
import tracered
import weights as W
import work

sys.path.insert(0, os.path.join(spec.ROOT, "src"))


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Window:
    """What the window measured, as the metric readers see it."""
    seconds: float
    setup_s: float
    slots: int
    chips: int
    images_in_window: int = 0
    attempted: int = 0
    answered: int = 0
    compiles_in_window: int = 0
    schedule: Optional[Dict] = None
    work: Optional[List[work.LayerWork]] = None   # per layer
    peaks: Optional[Dict[str, float]] = None
    trace: Optional[tracered.Reduction] = None


def require_chips(chips: int) -> List:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"first device is {devs[0].platform}, not a TPU")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX sees "
                     f"{len(devs)}")
    return devs[:chips]


def enable_caches() -> str:
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    path = enable_compile_cache()
    # every program of the run, small ones too, comes from the cache after
    # the first run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def build_model(config: dict, filters: List[np.ndarray], chips: int):
    """The served model: the program's offline chain (balance, fold, pack)
    over the pruned filters, laid out as the configuration's layer table."""
    from repro.sparsity.conv import build_sparse_chain
    from repro.vision.model import VisionLayer, VisionModel
    chain = build_sparse_chain(filters, density=1.0,
                               pattern=config["pattern"],
                               mesh_devices=chips if chips > 1 else None)
    layers = [VisionLayer(conv, (l["stride"], l["stride"]), l["padding"],
                          tuple(l["pool_after"]) if l["pool_after"] else None)
              for conv, l in zip(chain, config["layers"])]
    return VisionModel(config["program_arch"], layers, config["input_size"],
                       config["filter_density"])


def make_server(cell: spec.Cell, seed: int):
    """Set-up's device part: the seed's weights, the packed model, and a
    ``VisionServer`` with its one bucket compiled and warm.
    Returns the server and the pruned filters (for the reference)."""
    from repro.serve.vision import VisionServer, WallClock
    from repro.vision import data_mesh
    filters = W.make_weights(cell.config, seed)
    model = build_model(cell.config, filters, cell.chips)
    mesh = data_mesh(cell.chips) if cell.chips > 1 else None
    server = VisionServer(model, num_slots=int(cell.traffic["slots"]),
                          buckets=(int(cell.config["input_size"]),),
                          clock=WallClock(), mesh=mesh)
    server.warmup()
    return server, filters


class SpanLog:
    """The harness's own host spans, on the wall clock in nanoseconds.
    They mark the window and the steps in the trace, since the profiler's
    host tracer is off (it costs as much host time as a VGG16 step)."""

    def __init__(self):
        self.spans: List[tuple] = []

    @contextlib.contextmanager
    def span(self, name: str):
        t0 = time.time_ns()
        try:
            yield
        finally:
            self.spans.append((name, t0, time.time_ns()))

    def plane(self, origin_ns: int) -> dict:
        """The spans as a host plane of a compact trace that starts at
        ``origin_ns``."""
        return {"name": "/host:bench", "lines": [{"name": "harness", "events": [
            [n, float(t0 - origin_ns), float(t1 - t0)]
            for n, t0, t1 in self.spans]}]}

    def diag(self) -> Dict[str, float]:
        """Longest step, longest time between two steps, and the garbage
        collector's passes, in the window."""
        steps = sorted((t0, t1) for n, t0, t1 in self.spans
                       if n == "bench.step")
        if not steps:
            return {}
        gaps = [b[0] - a[1] for a, b in zip(steps, steps[1:])]
        gc_ms = [(t1 - t0) / 1e6 for n, t0, t1 in self.spans
                 if n.startswith("gc.")]
        return {"steps": len(steps),
                "max_step_ms": max(t1 - t0 for t0, t1 in steps) / 1e6,
                "max_gap_ms": max(gaps, default=0) / 1e6,
                "gc_passes": len(gc_ms), "gc_max_ms": max(gc_ms, default=0.0),
                "gc_total_ms": sum(gc_ms)}

    @contextlib.contextmanager
    def gc_spans(self):
        """Record each pass of the garbage collector as a span ``gc.<gen>``."""
        import gc
        start = {}

        def on_gc(phase, info):
            if phase == "start":
                start["t"] = time.time_ns()
            elif "t" in start:
                self.spans.append((f"gc.{info['generation']}",
                                   start.pop("t"), time.time_ns()))
        gc.callbacks.append(on_gc)
        try:
            yield
        finally:
            gc.callbacks.remove(on_gc)


class CompileCounter:
    """Counts traces and backend compiles in a ``with`` block."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.count = 0

    def _on(self, event: str, duration: float, **_) -> None:
        if event in self.EVENTS:
            self.count += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)
        return False


def drive(server, traffic: dict, pool: loadgen.ImagePool, seconds: float,
          sample: check.Reservoir, win: Window, annotate) -> None:
    """The measured window of the closed loop, then the drain of what is
    still open."""
    from repro.serve.vision import WallClock
    from repro.vision import ImageRequest
    images: Dict[int, np.ndarray] = {}
    rid = 0

    def submit(t: float) -> None:
        nonlocal rid
        img = pool.image()
        images[rid] = img
        server.submit(ImageRequest(rid=rid, image=img, arrival_s=t))
        rid += 1

    def collect(resubmit: bool) -> None:
        done = server.produced
        server.produced = {}
        for r, answer in done.items():
            rec = server.records[r]
            win.answered += 1
            if rec.done_s <= seconds:
                win.images_in_window += 1
            img = images.pop(r)
            j = sample.slot()
            if j is not None:
                sample.items[j] = (r, img, np.array(answer))
            if resubmit and rec.done_s < seconds:
                submit(rec.done_s)

    n_clients = loadgen.clients(traffic)
    with annotate("bench.window"):
        server.clock = clock = WallClock()
        for _ in range(n_clients):
            submit(0.0)
        while clock.now() < seconds:
            with annotate("bench.step"):
                server.step()
            with annotate("bench.collect"):
                collect(resubmit=True)
    while server.step():
        collect(resubmit=False)
    collect(resubmit=False)
    win.attempted = rid


def judge(reading: float, limit: Optional[float], checked: int, k: int,
          answered: int, failed: int) -> bool:
    """The predicate that decides ``correct``: the sample's reading within
    the limit, the whole sample checked, and every request answered."""
    return (limit is not None and reading <= limit
            and checked >= min(k, answered) > 0 and failed == 0)


def run(cell: spec.Cell, seed: int, seconds: float, trace: bool, *,
        t_start: float, require_chip: bool = True,
        keep_trace: Optional[str] = None, control: bool = False) -> dict:
    """One run; returns the result line as a dict. Raises NoChip.

    ``control`` adds the control of the check, which benchmark runs do
    not compute: ``control_max_rel_err``, the reference in three bf16
    passes put in the program's place, on the same sample;
    ``control_correct``, that reading judged by the predicate and limit
    that decide ``correct``; and ``native_high_max_rel_err``, the same at
    the backend's own ``Precision.HIGH``."""
    import jax
    devices = require_chips(cell.chips) if require_chip else \
        jax.devices()[:cell.chips]
    enable_caches()

    config, traffic = cell.config, cell.traffic
    server, filters = make_server(cell, seed)
    rng = np.random.default_rng([seed, 0])
    pool = loadgen.ImagePool(rng, traffic, config)
    sample = check.Reservoir(int(config["check_answers"]),
                             np.random.default_rng([seed, 1]))
    win = Window(seconds=seconds, setup_s=0.0, slots=int(traffic["slots"]),
                 chips=cell.chips)

    spans = SpanLog()
    log_dir = None
    if trace:
        import jax.profiler as prof
        log_dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = prof.ProfileOptions()
        opts.python_tracer_level = 0
        # the host tracer records each chunk of the input's layout
        # transpose (a million events in 5 s of VGG16) and doubles the step
        opts.host_tracer_level = 0
        prof.start_trace(log_dir, profiler_options=opts)
    win.setup_s = time.monotonic() - t_start
    with CompileCounter() as compiles, spans.gc_spans():
        drive(server, traffic, pool, seconds, sample, win, spans.span)
    win.compiles_in_window = compiles.count
    if trace:
        jax.profiler.stop_trace()

    memory_peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use",
                                                        0))
                      for d in devices)
    win.schedule = server.schedule_counters()
    win.peaks = spec.peaks(devices[0].device_kind) if require_chip else None
    win.work = work.chain_work(config, config["input_size"],
                               work.nonzero_counts(filters))
    breakdown = None
    if trace:
        raw = tracered.compact(tracered.find_xplane(log_dir))
        shutil.rmtree(log_dir, ignore_errors=True)
        raw["planes"].append(spans.plane(raw["profile_start_ns"]))
        if keep_trace:
            _save(raw, keep_trace)
        win.trace = tracered.reduce(raw, [d.id for d in devices])
        del raw
        breakdown = {"device_ops": win.trace.top_ops(),
                     "idle_gaps": win.trace.longest_gaps()}

    # the check: the program's state goes first, then the reference runs
    del server
    check.free_device_memory()
    ids = [r for r, _, _ in sample.items]
    ref_module = spec.reference(config)
    sampled = [img for _, img, _ in sample.items]
    refs = check.reference_answers(ref_module, config, filters, sampled)
    reading = check.max_rel_err([a for _, _, a in sample.items], refs)
    limit = config["limits"]["max_rel_err"]
    failed = win.attempted - win.answered
    controls = {}
    if control:
        for key, prec in (("control_max_rel_err", "high3"),
                          ("native_high_max_rel_err", "high")):
            controls[key] = check.max_rel_err(
                check.reference_answers(ref_module, config, filters, sampled,
                                        precision=prec), refs)
        controls["control_correct"] = judge(
            controls["control_max_rel_err"], limit, len(ids), sample.k,
            win.answered, failed)
    checks = {
        "max_rel_err": {"value": reading, "limit": limit},
        "answers_checked": {"value": len(ids),
                            "limit": min(sample.k, win.answered)},
        "unanswered": {"value": failed, "limit": 0},
    }
    correct = judge(reading, limit, len(ids), sample.k, win.answered,
                    failed)

    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = m.read(win)
        if v is not None:
            metrics[m.name] = {"value": v, "unit": m.unit}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    if trace:
        device["busy_s"] = win.trace.busy_s
        device["window_s"] = win.trace.window_s
    out = {"correct": bool(correct), "attempted": win.attempted,
           "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["compiles_in_window"] = win.compiles_in_window
    out["window_diag"] = spans.diag()
    out.update(controls)
    out["checks"] = checks
    return out


def _save(raw: dict, path: str) -> None:
    import gzip
    import json
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with gzip.open(path, "wt") as f:
        json.dump(raw, f)
