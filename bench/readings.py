#!/usr/bin/env python3
"""Readings that set a cell's limit: the program's ``max_rel_err`` and the
control's on many seeds, in one process (each seed still compiles its own
forward, since the weights are baked into the program).

    python3 bench/readings.py --workload <cell> --seconds 2 \
        --seeds 101 102 ... [--out FILE]

Each seed runs the cell as ``run.py`` does, at its own load for
``--seconds``, and prints one JSON line: the seed, the program's reading
and ``correct``, the control's reading on the same sampled answers and
``control_correct`` (the control judged as ``correct`` is judged, against
the same limit; it has to come out false), and the metrics. Needs the
cell's chips.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    import harness
    import spec
    cell = spec.load_cell(args.workload)
    rows = []
    t = T_START
    for seed in args.seeds:
        try:
            out = harness.run(cell, seed, args.seconds, False, t_start=t,
                              control=True)
        except harness.NoChip as e:
            print(f"readings: {e}", file=sys.stderr)
            return 2
        row = {"workload": args.workload, "seed": seed,
               "max_rel_err": out["checks"]["max_rel_err"]["value"],
               "control_max_rel_err": out["control_max_rel_err"],
               "correct": out["correct"],
               "control_correct": out["control_correct"],
               "native_high_max_rel_err": out["native_high_max_rel_err"],
               "answers_checked": out["checks"]["answers_checked"]["value"],
               "attempted": out["attempted"], "failed": out["failed"],
               "metrics": out["metrics"], "device": out["device"]}
        print(json.dumps(row), flush=True)
        rows.append(row)
        t = time.monotonic()
    if args.out:
        with open(args.out, "a") as f:
            for row in rows:
                f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
