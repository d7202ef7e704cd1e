"""Fig. 11-13 analogues: execution time vs data size; parallel scaling.

Wall-clock numbers come from ONE CPU core, so absolute times are not
TPU-meaningful; the *trends* (PRF vs RF slope with data size, Fig. 11)
are. Parallel speedup (Fig. 12-13) is derived from the compiled
artifacts (per-device FLOPs ratio vs 1 device), consistent with the
dry-run methodology — a single host core cannot time 8 virtual devices
honestly.
"""
import json
import subprocess
import sys
import textwrap
import time

import numpy as np

from repro.core import ForestConfig, train_prf
from repro.core.baselines import train_rf
from repro.data.tabular import make_classification


def fig11_time_vs_datasize(sizes=(1000, 4000, 16000)):
    rows = []
    for n in sizes:
        x, y = make_classification(n_samples=n, n_features=100, n_classes=3, seed=0)
        cfg = ForestConfig(n_trees=16, max_depth=6, n_bins=16, n_classes=3)
        for name, fn in [("prf", train_prf), ("rf", train_rf)]:
            fn(x, y, cfg, seed=0)              # warm the jit cache
            t0 = time.time()
            fn(x, y, cfg, seed=1)
            rows.append({
                "bench": "fig11_time_vs_datasize", "algo": name, "n_samples": n,
                "seconds": time.time() - t0,
                "us_per_call": (time.time() - t0) * 1e6,
            })
    return rows


_SCALING = textwrap.dedent("""
    import os
    os.environ["JAX_PLATFORMS"] = "cpu"  # a CPU drill: never the parent's chip
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    from repro.core import ForestConfig
    from repro.core.distributed import make_prf_train_fn
    from repro.launch.mesh import make_mesh
    from repro.roofline.analysis import analyze_hlo_text

    N, F, C = 1 << 14, 256, 4
    cfg = ForestConfig(n_trees=16, max_depth=6, n_bins=16, n_classes=C,
                       max_frontier=8, tree_chunk=8)
    out = []
    for shape in [(1, 1), (2, 2), (4, 2), (4, 4) if False else (2, 4)]:
        n_dev = shape[0] * shape[1]
        mesh = make_mesh(shape, ("data", "model"))
        fn, _ = make_prf_train_fn(cfg, mesh)
        comp = fn.lower(jax.ShapeDtypeStruct((N, F), jnp.uint8),
                        jax.ShapeDtypeStruct((N,), jnp.int32),
                        jax.ShapeDtypeStruct((2,), jnp.uint32)).compile()
        a = analyze_hlo_text(comp.as_text())
        out.append({"devices": n_dev, "flops_per_device": a["flops"],
                    "collective_mb": a["collective_bytes"] / 2**20})
    print("RESULT" + json.dumps(out))
""")


def fig13_parallel_scaling():
    p = subprocess.run([sys.executable, "-c", _SCALING], capture_output=True,
                       text=True, timeout=1800)
    if p.returncode != 0:
        return [{"bench": "fig13_scaling", "error": p.stderr[-500:], "us_per_call": 0.0}]
    line = [l for l in p.stdout.splitlines() if l.startswith("RESULT")][-1]
    rows = json.loads(line[len("RESULT"):])
    base = rows[0]["flops_per_device"]
    out = []
    for r in rows:
        speedup = base / r["flops_per_device"] if r["flops_per_device"] else 0.0
        out.append({
            "bench": "fig13_scaling", "devices": r["devices"],
            "flops_per_device": r["flops_per_device"],
            "derived_speedup": speedup,
            "parallel_efficiency": speedup / r["devices"],
            "us_per_call": 0.0,
        })
    return out


def run():
    return fig11_time_vs_datasize() + fig13_parallel_scaling()
