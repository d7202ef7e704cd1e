"""Where a training cell's time goes, by the program's own spans and scopes.

    python3 bench/scopes.py --workload higgs-train --seed <n> [--seconds 10]

Sets the cell up as a run does, traces a window of whole jobs with the
JAX profiler and reduces the trace by the program's ``prf.`` names
(``harness/scopes.py``): device time per job under each scope, host time
per job in each span, and the idle gaps named after the host span open
over each piece of them. It also prints the split of the growth engine's
non-kernel time (``engine.xla_ms_per_job``) into the level step's scopes
and what no scope covers. One JSON line. Needs the chip, like ``run.py``.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import run  # noqa: E402
from harness import trace  # noqa: E402

TASK_GROUP = ("prf.task_group", "prf.tgr", "prf.tns")
PREP = ("prf.bin.apply", "prf.dsi", "prf.dimred")


def split(sc, jobs: int) -> dict:
    """Per-job numbers of a traced window of ``jobs`` training jobs."""
    tr = sc.trace
    ms = lambda s: 1e3 * s / tr.n_devices / jobs  # noqa: E731
    grow = lambda o: "grow_forest_impl" in o.module and not o.kernel  # noqa: E731
    by_scope: dict = {}
    for o in tr.ops:
        key = f"{o.module}/{sc.scope(o) or '-'}[{'kernel' if o.kernel else 'xla'}]"
        by_scope[key] = by_scope.get(key, 0.0) + o.dur
    engine = {
        "engine.xla_ms_per_job": ms(tr.op_seconds(grow)),
        "engine.task_group_xla_ms_per_job": ms(sc.op_seconds(
            lambda o, s: not o.kernel and s in TASK_GROUP)),
        "engine.route_ms_per_job": ms(sc.op_seconds(
            lambda o, s: not o.kernel and s == "prf.route")),
        "engine.plan_write_ms_per_job": ms(sc.op_seconds(
            lambda o, s: not o.kernel and s == "prf.plan_write")),
        "engine.unscoped_ms_per_job": ms(sc.op_seconds(lambda o, s: grow(o) and not s)),
    }
    names = sorted({n for n, _, _ in sc.spans if n.startswith("prf.")})
    return {
        "jobs": jobs,
        "window_s": tr.window_s,
        "idle_ms_per_job": ms(tr.window_s * tr.n_devices - sum(tr.busy_s)),
        **engine,
        "oob.walk_ms_per_job": ms(sc.op_seconds(lambda o, s: s == "prf.walk")),
        "prep.device_ms_per_job": ms(sc.op_seconds(lambda o, s: s in PREP)),
        "host_ms_per_job": {n: 1e3 * sc.span_seconds(n) / jobs for n in names},
        "device_ms_per_job": {k: ms(v) for k, v in
                              sorted(by_scope.items(), key=lambda kv: -kv[1])[:24]},
        "idle_gaps_ms_per_job": [[k, ms(v)] for k, v in trace.top(sc.gaps())],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)

    from harness import device, drivers, scopes

    spec = run.resolve(run.load_json(run.ROOT, "BENCHMARK.json"), args.workload)
    try:
        devices = device.require(spec["cell"]["chips"])
    except device.NoChip as e:
        print(f"scopes: {e}; nothing run", file=sys.stderr)
        return 3
    info = device.describe(devices)
    run.enable_compile_cache()
    import jax

    drv = drivers.load(spec["traffic"]["kind"])(
        spec["cell"], spec["config"], spec["traffic"], args.seed, args.seconds, devices)
    if not isinstance(drv, drivers.TrainJobs):
        print("scopes: only training cells", file=sys.stderr)
        return 2
    drv.setup()
    tmp = tempfile.mkdtemp(prefix="bench-scopes-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(tmp, profiler_options=opts)
    drv.window(args.seconds)
    jax.profiler.stop_trace()
    sc = scopes.load(tmp)
    shutil.rmtree(tmp, ignore_errors=True)
    print(json.dumps({"workload": args.workload, "seed": args.seed, "device": info,
                      **split(sc, len(drv.jobs))}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
