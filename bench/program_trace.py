#!/usr/bin/env python3
"""The program's own spans and the forward's per-layer name scopes, as a
traced benchmark run reads them, printed in full.

    python3 bench/program_trace.py --workload <cell> --seconds 20 \
        --seeds <n> [<n> ...] [--trace 0|1] [--obs 0|1] \
        [--keep-trace FILE] [--out FILE]

``--trace 1`` (the default) is ``run.py --trace 1`` (``harness.run``),
and prints per seed one JSON line built from that run's own ``Window``:
the step's phases (``serve.*``), the set-up's parts, device time per
layer and part, the idle gaps named by the innermost host span (the
program's included), the readings of ``host_ms_per_step``,
``im2col_ms_per_img``, ``pack_s``, ``warmup_s`` and ``compile_s`` as
``programread`` gives them, and the run's metrics beside them.
``--keep-trace`` saves the first seed's compact trace, with the
harness's and the program's host planes. ``--trace 0`` is ``run.py
--trace 0`` with the recorder forced on (``--obs 1``) or left off: the
recorder's cost. Needs the cell's chips.
"""
import time

T_START = time.monotonic()

import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import programread as PR  # noqa: E402


def traced_run(cell, seed: int, seconds: float, *, t_start: float,
               keep_trace=None) -> dict:
    """``run.py --trace 1`` (``harness.run``), and what its window read."""
    import harness
    seen = []
    out = harness.run(cell, seed, seconds, True, t_start=t_start,
                      keep_trace=keep_trace, on_window=seen.append)
    w = seen[0]
    snap, win = w.program, w.window_ns
    return {
        "workload": cell.name, "seed": seed, "correct": out["correct"],
        "img_per_s": w.images_in_window / seconds,
        # answers done by the window's end, and the images of the steps
        # that start and end in it
        "images": [w.images_in_window, PR.images_in_window(snap, win)],
        "readings": {
            "host_ms_per_step": PR.host_ms_per_step(snap, win),
            "im2col_ms_per_img": PR.im2col_ms_per_img(
                w.scope_s, w.images_in_window),
            "pack_s": PR.pack_s(snap, win[0]),
            "warmup_s": PR.warmup_s(snap, win[0]),
            "compile_s": PR.compile_s(snap, win[0])},
        "metrics": {k: v["value"] for k, v in out["metrics"].items()},
        "step_phases": PR.step_phases(snap, win),
        "setup_phases": PR.setup_phases(snap, win[0]),
        "layers": PR.layers(w.scope_s, w.images_in_window),
        "idle_gaps": w.trace.longest_gaps(),
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
        row["step_phases"] = PR.step_phases(snap, (0, float("inf")))
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
                    help="the first seed's compact trace, gzipped JSON")
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
