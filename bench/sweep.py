"""Find the serving knee once: one serving cell's traffic at a list of rates.

    python3 bench/sweep.py --workload higgs-serve --seed 7 --seconds 10 \
        --rates 500 1000 2000 4000

Sets the cell up once (forest, service, warm-up of every pass size),
then runs the cell's open-loop window at each offered rate and prints
one JSON line per rate: completions per second, latency percentiles
from due to done, the generator's lateness and whether the backlog grew
(the p95 of the window's last third against its first). The cell's
``rate_per_s`` is then fixed by hand at about 0.8 of the highest rate
that holds the latency limit with no growing backlog. Needs the chip,
like ``run.py``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="higgs-serve")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)

    from harness import device, drivers

    spec = run.resolve(run.load_json(run.ROOT, "BENCHMARK.json"), args.workload)
    try:
        devices = device.require(spec["cell"]["chips"])
    except device.NoChip as e:
        print(f"sweep: {e}; nothing run", file=sys.stderr)
        return 3
    device.describe(devices)
    run.enable_compile_cache()
    t = time.perf_counter()
    traffic = dict(spec["traffic"], rate_per_s=args.rates[0])
    drv = drivers.load(traffic["kind"])(spec["cell"], spec["config"], traffic, args.seed,
                                        args.seconds, devices)
    drv.setup()
    print(f"sweep: set-up {time.perf_counter() - t} s", file=sys.stderr, flush=True)
    for rate in args.rates:
        drv.traffic["rate_per_s"] = rate
        drv.requests = drv.schedule()
        drv.window(args.seconds)
        served = drv.served()
        lat = np.array([(r.done - r.due) * 1e3 for r in served])
        due = np.array([r.due for r in served])
        first, last = lat[due < args.seconds / 3], lat[due >= 2 * args.seconds / 3]
        late = [(r.sent - r.due) * 1e3 for r in drv.requests if r.sent is not None]
        row = {
            "rate_per_s": rate,
            "requests": len(drv.requests),
            "failed": len(drv.requests) - len(served),
            "completed_per_s": len(served) / drv.window_s,
            "p50_ms": float(np.percentile(lat, 50)),
            "p95_ms": float(np.percentile(lat, 95)),
            "p99_ms": float(np.percentile(lat, 99)),
            "p95_first_third_ms": float(np.percentile(first, 95)),
            "p95_last_third_ms": float(np.percentile(last, 95)),
            "gen_late_p95_ms": float(np.percentile(late, 95)),
            "passes": len(drv.groups()),
            "rows_per_pass": float(np.mean(drv.groups())),
        }
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
