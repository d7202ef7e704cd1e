"""Fig. 15 analogue: data-communication cost, vertical vs horizontal
partitioning, vs cluster scale.

Methodology matches the dry-run: lower + compile the distributed PRF
trainer under each partitioning, parse per-device collective bytes from
the post-SPMD HLO. "Horizontal" = all devices shard samples, features
replicated (Spark-MLRF's layout): every histogram psum moves full-F
stats across the whole cluster. "Vertical" (the paper's scheme) psums
F/m-sized stats across the sample axis only.

Runs in a subprocess (needs host-device mesh).
"""
import json
import subprocess
import sys
import textwrap

_CODE = textwrap.dedent("""
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
    for n_dev, shape, axes in [
        (2, (2, 1), "h"), (4, (4, 1), "h"), (8, (8, 1), "h"),
        (2, (1, 2), "v"), (4, (2, 2), "v"), (8, (2, 4), "v"),
    ]:
        mesh = make_mesh(shape, ("data", "model"))
        fn, _ = make_prf_train_fn(cfg, mesh)
        xb = jax.ShapeDtypeStruct((N, F), jnp.uint8)
        y = jax.ShapeDtypeStruct((N,), jnp.int32)
        key = jax.ShapeDtypeStruct((2,), jnp.uint32)
        comp = fn.lower(xb, y, key).compile()
        a = analyze_hlo_text(comp.as_text())
        out.append({"layout": "horizontal" if axes == "h" else "vertical",
                    "devices": n_dev,
                    "collective_mb_per_device": a["collective_bytes"] / 2**20,
                    "collective_ops": {k: int(v["count"]) for k, v in a["collectives"].items()}})
    print("RESULT" + json.dumps(out))
""")


def run():
    p = subprocess.run([sys.executable, "-c", _CODE], capture_output=True,
                       text=True, timeout=1800)
    if p.returncode != 0:
        return [{"bench": "fig15_comm", "error": p.stderr[-500:], "us_per_call": 0.0}]
    line = [l for l in p.stdout.splitlines() if l.startswith("RESULT")][-1]
    rows = []
    for r in json.loads(line[len("RESULT"):]):
        rows.append({"bench": "fig15_comm", **r, "us_per_call": 0.0})
    return rows
