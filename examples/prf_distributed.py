"""Distributed PRF on a host-device mesh — the paper's §4 in miniature.

    python examples/prf_distributed.py --devices 8 --data 4 --model 2

Vertical partitioning: features shard over `model`, samples over `data`;
T_GR histogram psum crosses only the sample axis, T_NS winner selection
only the feature axis (paper Figs. 3-7).

Multi-process mode — the cluster topology on one machine:

    python examples/prf_distributed.py --multiproc 2 --local-devices 2

spawns N coordinator-connected ``jax.distributed`` processes, each
feeding only its own row range of a shared memmap through
``launch.multiproc.MultiHostMesh``; every process prints its per-host
feed bytes and the (identical) global forest hash.
"""
import argparse
import hashlib
import os
import subprocess
import sys
import tempfile


def _forest_hash(model) -> str:
    import jax
    import numpy as np

    h = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(model.forest):
        h.update(np.asarray(leaf).tobytes())
    h.update(np.asarray(model.bin_edges).tobytes())
    return h.hexdigest()


def run_multiproc_worker(args):
    """One coordinator-connected training process of the drill."""
    sys.path.insert(0, "src")
    from repro.launch import multiproc

    pid, nproc = multiproc.initialize(
        f"127.0.0.1:{args.port}", args.multiproc, args.worker,
        local_device_count=args.local_devices,
    )
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    import numpy as np

    from repro.core import ForestConfig
    from repro.core.api import train_prf
    from repro.launch.multiproc import MultiHostMesh

    x = np.memmap(args.memmap, dtype=np.float32, mode="r",
                  shape=(args.rows, args.features))
    y = np.load(args.memmap + ".y.npy")
    cfg = ForestConfig(
        n_trees=args.trees, max_depth=6, n_bins=32, n_classes=4,
        feature_mode="importance", weighted_voting=True,
        sample_block=args.rows // 4,
    )
    runtime = MultiHostMesh()
    from repro.core.distributed import train_prf_multiproc

    model = train_prf_multiproc(x, y, cfg, seed=0, runtime=runtime)
    lo, hi = runtime.local_row_range(
        args.rows + runtime.pad(args.rows)
    )
    print(
        f"[proc {pid}/{nproc}] data shards [{runtime.shard_lo}, "
        f"{runtime.shard_hi}) rows ~[{lo}, {hi}) fed "
        f"{runtime.feed_bytes / 2**20:.2f} MiB host->device",
        flush=True,
    )
    print(f"[proc {pid}/{nproc}] forest sha256={_forest_hash(model)}",
          flush=True)


def run_multiproc(args):
    """Spawn the coordinator-connected process fleet and check parity."""
    sys.path.insert(0, "src")
    import numpy as np

    from repro.data.tabular import make_classification

    x, y = make_classification(
        n_samples=args.rows, n_features=args.features, n_classes=4, seed=1,
    )
    tmp = tempfile.mkdtemp(prefix="prf_multiproc_")
    path = os.path.join(tmp, "train.f32")
    mm = np.memmap(path, dtype=np.float32, mode="w+", shape=x.shape)
    mm[:] = x.astype(np.float32)
    mm.flush()
    np.save(path + ".y.npy", y)

    procs = [
        subprocess.Popen(
            [sys.executable, __file__, "--worker", str(i),
             "--multiproc", str(args.multiproc),
             "--local-devices", str(args.local_devices),
             "--port", str(args.port), "--memmap", path,
             "--rows", str(args.rows), "--features", str(args.features),
             "--trees", str(args.trees)],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            # A CPU drill: the workers never compete for an accelerator.
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        for i in range(args.multiproc)
    ]
    hashes = []
    for i, p in enumerate(procs):
        out, _ = p.communicate(timeout=900)
        print(out, end="")
        if p.returncode != 0:
            raise SystemExit(f"worker {i} failed (rc={p.returncode})")
        hashes += [ln.rsplit("=", 1)[1] for ln in out.splitlines()
                   if "forest sha256=" in ln]
    if len(set(hashes)) != 1:
        raise SystemExit(f"forest hashes diverged across hosts: {hashes}")
    print(f"global forest hash agrees across {args.multiproc} processes: "
          f"{hashes[0][:16]}…")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--data", type=int, default=4)
    ap.add_argument("--model", type=int, default=2)
    ap.add_argument("--trees", type=int, default=16)
    ap.add_argument("--multiproc", type=int, default=0,
                    help="spawn N jax.distributed processes instead of the "
                         "single-process mesh demo")
    ap.add_argument("--local-devices", type=int, default=2)
    ap.add_argument("--port", type=int, default=12737)
    ap.add_argument("--rows", type=int, default=4096)
    ap.add_argument("--features", type=int, default=64)
    ap.add_argument("--worker", type=int, default=None,
                    help=argparse.SUPPRESS)
    ap.add_argument("--memmap", type=str, default=None,
                    help=argparse.SUPPRESS)
    args = ap.parse_args()

    if args.worker is not None:
        run_multiproc_worker(args)
        return
    if args.multiproc:
        run_multiproc(args)
        return

    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={args.devices}"
    )
    sys.path.insert(0, "src")

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import ForestConfig
    from repro.core.binning import apply_bins
    from repro.core.distributed import (
        fit_bins_sharded, make_prf_train_fn, predict_sharded,
    )
    from repro.data.tabular import make_classification, train_test_split
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_mesh
    from repro.roofline.analysis import analyze_hlo_text

    enable_compile_cache()

    x, y = make_classification(n_samples=4096, n_features=64, n_classes=4, seed=1)
    xtr, ytr, xte, yte = train_test_split(x, y, 0.25, 0)
    cfg = ForestConfig(n_trees=args.trees, max_depth=6, n_bins=32, n_classes=4)

    mesh = make_mesh((args.data, args.model), ("data", "model"))
    print(f"mesh: data={args.data} x model={args.model}")
    # Bin edges from per-shard quantile sketches merged over the mesh —
    # no single host ever takes a full pass over the raw source.
    edges = fit_bins_sharded(xtr, cfg.n_bins, mesh, sample_block=512)
    xb = np.asarray(apply_bins(jnp.asarray(xtr), jnp.asarray(edges)))
    train_fn, _ = make_prf_train_fn(cfg, mesh)

    n = (xb.shape[0] // args.data) * args.data
    lowered = train_fn.lower(
        jnp.asarray(xb[:n]), jnp.asarray(ytr[:n]), jax.random.PRNGKey(0)
    )
    compiled = lowered.compile()
    a = analyze_hlo_text(compiled.as_text())
    print("collectives (per device):",
          {k: int(v["count"]) for k, v in a["collectives"].items()},
          f"= {a['collective_bytes']/2**20:.1f} MiB on the wire")

    forest = train_fn(jnp.asarray(xb[:n]), jnp.asarray(ytr[:n]), jax.random.PRNGKey(0))
    xbte = apply_bins(jnp.asarray(xte), jnp.asarray(edges))
    m = (xbte.shape[0] // args.data) * args.data
    pred = predict_sharded(forest, xbte[:m], mesh)
    acc = float(np.mean(np.asarray(pred) == yte[:m]))
    print(f"distributed PRF accuracy: {acc:.4f}")


if __name__ == "__main__":
    main()
