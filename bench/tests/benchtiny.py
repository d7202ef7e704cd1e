"""Tiny versions of the benchmark's cells and of the ones kept ready for
later PRs, for runs on the CPU."""
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import run  # noqa: E402

TINY_CONFIG = {
    "higgs": {"n_rows": 2048, "max_depth": 4, "n_trees": 4},
    "epsilon": {"n_rows": 1024, "n_features": 64, "max_depth": 3, "n_trees": 4,
                "sketch_max_size": 512},
}
TINY_TRAFFIC = {
    "serve_open": {"max_batch": 64, "rows_min": 2, "rows_max": 16, "rate_per_s": 200,
                   "pool_rows": 4096, "edge_rows": 4096},
}


CELLS = {
    "higgs-train": ("higgs", "train_jobs", 1),
    "higgs-serve": ("higgs", "serve_open", 1),
    "epsilon-train-mesh4": ("epsilon", "mesh_train_jobs", 4),
}


def spec(cell: str) -> dict:
    """A run's spec from the configuration and traffic files, at a tiny size."""
    config, traffic, chips = CELLS[cell]
    s = {
        "cell": {"name": cell, "config": config, "traffic": traffic, "chips": chips},
        "config": run.load_json(BENCH, "configs", f"{config}.json"),
        "traffic": run.load_json(BENCH, "traffic", f"{traffic}.json"),
        "end_to_end": [
            {"name": "train_rowtrees_per_s" if traffic != "serve_open" else "serve_p95_ms",
             "unit": "1"},
            {"name": "setup_s", "unit": "s"},
        ],
        "per_layer": [],
    }
    s["config"].update(TINY_CONFIG[config])
    s["traffic"].update(TINY_TRAFFIC.get(traffic, {}))
    return s


def execute(cell: str, seed: int = 2**31 + 77, seconds: float = 1.0, traced: bool = False):
    import jax

    s = spec(cell)
    devices = jax.devices()[: s["cell"]["chips"]]
    info = {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)["devices"]["TPU v5 lite"]
    return run.execute(s, seed, seconds, traced, devices, info, peaks,
                       t_start=__import__("time").perf_counter())
