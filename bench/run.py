"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout. The cell, its configuration
(``bench/configs/<config>.json``), its traffic
(``bench/traffic/<traffic>.json``, whose ``kind`` names its driver,
``bench/kinds/<kind>.py``) and its per-layer metrics
(``bench/metrics/<metric>.py``) are found by the names in
``BENCHMARK.json``. Without a TPU, or with fewer chips than the cell
asks for, the run exits non-zero before any work and prints no result.

The run makes its inputs from ``--seed``, warms up every shape its
window uses (``setup_s``: process start to window start), measures for
``--seconds``, and then compares what the window produced with the
plain reference (``bench/harness/reference.py``). ``--trace 0`` reports
the cell's end-to-end metrics; ``--trace 1`` traces the window with the
JAX profiler and reports the cell's per-layer metrics instead. The last
line of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` ``breakdown``,
and last ``checks``, every number compared beside its limit; the same
numbers are the last lines of standard error.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_metric(name: str):
    """The reader module of a per-layer metric, ``bench/metrics/<name>.py``."""
    path = os.path.join(BENCH, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve(bench: dict, workload: str) -> dict:
    """The cell, its configuration, traffic and the metrics it reports."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    e2e = [m for m in bench["end_to_end"] if workload in m.get("workloads", [workload])]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (workload in m["workloads"] if "workloads" in m else m["moves"] in moved)]
    return {
        "cell": cell,
        "config": load_json(BENCH, "configs", f"{cell['config']}.json"),
        "traffic": load_json(BENCH, "traffic", f"{cell['traffic']}.json"),
        "end_to_end": e2e,
        "per_layer": per_layer,
    }


def enable_compile_cache() -> str:
    """The program's persistent compilation cache, inside the checkout
    (or where ``JAX_COMPILATION_CACHE_DIR`` says)."""
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache as enable

    return enable()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    spec = resolve(load_json(ROOT, "BENCHMARK.json"), args.workload)
    cell = spec["cell"]

    from harness import device

    try:
        devices = device.require(cell["chips"])
    except device.NoChip as e:
        print(f"bench: {e}; nothing run", file=sys.stderr)
        return 3
    dev_info = device.describe(devices)
    peaks = device.peaks(dev_info["kind"])
    print(f"bench: compile cache {enable_compile_cache()}", file=sys.stderr)
    result = execute(spec, args.seed, args.seconds, bool(args.trace), devices, dev_info, peaks)
    for k, c in result["checks"].items():
        print(f"check {k}: {c['value']} (limit {c['limit']})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


def execute(spec: dict, seed: int, seconds: float, traced: bool, devices, dev_info: dict,
            peaks: dict, *, t_start: float = T_PROCESS) -> dict:
    """Set up, measure, compare: the whole run after the device check.
    Returns the result object."""
    sys.path.insert(0, os.path.join(ROOT, "src"))

    import jax

    from harness import device, drivers, trace
    from harness.clock import Compiles, now

    compiles = Compiles()
    drv = drivers.load(spec["traffic"]["kind"])(
        spec["cell"], spec["config"], spec["traffic"], seed, seconds, devices)
    drv.setup()
    setup_s = now() - t_start
    print(f"bench: set-up {setup_s} s, {compiles.count} compiles "
          f"({compiles.seconds} s)", file=sys.stderr, flush=True)

    if traced:
        tmp = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(tmp, profiler_options=opts)
    c0 = compiles.count
    drv.window(seconds)
    in_window = compiles.count - c0
    tr = None
    if traced:
        jax.profiler.stop_trace()
        tr = trace.load(tmp)
        shutil.rmtree(tmp, ignore_errors=True)
    print(f"bench: window {drv.window_s} s, {in_window} compiles inside it",
          file=sys.stderr, flush=True)

    dev_info = dict(dev_info, memory_peak_bytes=device.memory_peak_bytes(devices))
    e2e = {**drv.end_to_end(), "setup_s": setup_s}
    attempted, failed = drv.attempted()
    rec = types.SimpleNamespace(
        cell=spec["cell"], cfg=spec["config"], traffic=spec["traffic"],
        shapes=drv.shapes(), peaks=peaks, chips=len(devices), trace=tr, driver=drv,
        window_s=drv.window_s,
    )
    if tr is not None:
        dev_info["busy_s"] = sum(tr.busy_s) / len(tr.busy_s)
        dev_info["window_s"] = tr.window_s
        metrics = {}
        for m in spec["per_layer"]:
            value = load_metric(m["name"]).read(rec)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in spec["end_to_end"] if m["name"] in e2e}

    drv.release()
    t = now()
    numbers = drv.check()
    print(f"bench: reference took {now() - t} s", file=sys.stderr)
    checks = {k: {"value": v, "limit": drv.limits[k]} for k, v in numbers.items()}
    correct = failed == 0 and all(c["value"] <= c["limit"] for c in checks.values())
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": dev_info}
    if tr is not None:
        result["breakdown"] = trace.breakdown(tr)
    result["checks"] = checks
    return result


if __name__ == "__main__":
    sys.exit(main())
