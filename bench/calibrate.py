"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 bench/calibrate.py --workload higgs-train --seeds 1 2 3 ... \
        [--control 3] [--witness 0] [--seconds 5]

For each seed, runs the cell's timed path as a run does (a training
cell: one job of the window's entry; a serving cell: a ``--seconds``
window at the cell's rate) and compares it with the plain reference:
the program's numbers, whose largest over a dozen seeds or more is the
lower reading of each limit. For the first ``--control`` seeds it also
puts the reference computed in bfloat16 (the control) in the program's
place: the smallest of those numbers is the upper reading. One JSON
line per seed, then the two readings. The benchmark's runs never run
the control. For the first ``--witness`` seeds of a training cell it
also grows the reference's own trees from the same inputs and scores,
at the first node where each tree differs, both decisions by the
reference's float64 gain ratios (``reference.first_differences``).
Needs the chip, like ``run.py``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--witness", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)

    from harness import device, drivers

    spec = run.resolve(run.load_json(run.ROOT, "BENCHMARK.json"), args.workload)
    try:
        devices = device.require(spec["cell"]["chips"])
    except device.NoChip as e:
        print(f"calibrate: {e}; nothing run", file=sys.stderr)
        return 3
    device.describe(devices)
    run.enable_compile_cache()
    kind = drivers.load(spec["traffic"]["kind"])
    lower, upper = {}, {}
    for i, seed in enumerate(args.seeds):
        t = time.perf_counter()
        drv = kind(spec["cell"], spec["config"], spec["traffic"], seed, args.seconds, devices)
        if isinstance(drv, drivers.TrainJobs):
            drv.prepare()
            drv.window(0.0)                 # exactly one job
        else:
            drv.setup()
            drv.window(args.seconds)
        drv.release()
        train = isinstance(drv, drivers.TrainJobs)
        row = {"seed": seed, "attempted_failed": drv.attempted(),
               "program": drv.check(detail=True) if train else drv.check()}
        if train:
            row["job_s"] = drv.jobs[0]["end"] - drv.jobs[0]["start"]
        if i < args.control:
            row["control"] = drv.control(detail=True) if train else drv.control()
            for k, v in row["control"].items():
                if isinstance(v, (int, float)):
                    upper[k] = min(upper.get(k, float("inf")), v)
        if train and i < args.witness:
            row["witness"] = drv.witness()
        for k, v in row["program"].items():
            if isinstance(v, (int, float)):
                lower[k] = max(lower.get(k, float("-inf")), v)
        row["seconds"] = time.perf_counter() - t
        print(json.dumps(row), flush=True)
        del drv
    print(json.dumps({"lower_reading": lower, "upper_reading": upper,
                      "seeds": len(args.seeds), "control_seeds": min(args.control,
                                                                     len(args.seeds))}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
