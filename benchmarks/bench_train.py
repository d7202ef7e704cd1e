"""Training-path benchmarks — the growth-engine trajectory rows.

``train_e2e_resident`` times the unified engine's jitted
``grow_forest`` (early-exit while_loop, whole dataset device-resident);
``train_e2e_streamed`` the host-streaming ``grow_forest_streamed``
driver on the same data split into 4 sample blocks with the
synchronous feed (``prefetch=0`` — the fused route+hist pass reads
each block once per level, but block copies still serialize with
compute); ``train_e2e_streamed_prefetch`` the full async data plane
(``prefetch=2``: a ``BlockFeeder`` thread keeps the next block's
host->device copy in flight while the current block's histogram
runs). ``oob_streamed`` times the blocked Eq. 8 OOB sweep against the
resident call (``resident_us``). ``train_early_exit`` times a
cleanly-separable dataset under a generous depth budget (trees purify
and their frontiers die at ~1/4 of ``max_depth`` — the realistic
over-budgeted case), with the fixed-depth time of the bit-identical
forest in ``fixed_depth_us`` — the level-count saving the early-exit
scheduler buys. Rows land in BENCH_kernels.json next to the kernel
series (see PERF.md); CI fails the kernels-bench job if the streamed
rows go missing.
"""
import dataclasses
import json
import os
import subprocess
import sys
import textwrap
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import grow_forest_streamed
from repro.core.binning import bin_dataset
from repro.core.dsi import bootstrap_counts
from repro.core.forest import grow_forest
from repro.core.types import ForestConfig
from repro.core.voting import oob_accuracy, oob_accuracy_streamed
from repro.data.tabular import make_classification

K, N, F, B, C, DEPTH = 8, 4096, 32, 16, 3, 6
N_BLOCKS = 4
SHAPE = f"k={K},N={N},F={F},B={B},C={C},depth={DEPTH}"

# Multi-process plane worker: one coordinator-connected jax.distributed
# process of the 2x2 drill, timing the full train_prf_multiproc pipeline
# (screen -> sharded sketch merge -> local binning -> growth) on the
# same global shape as train_e2e_streamed. Spawned twice by
# run_multiproc(); process 0 prints the warm-call RESULT line.
_MP_CODE = textwrap.dedent("""
    import os
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = ""
    pid = int(os.environ["PRF_PID"])
    nproc = int(os.environ["PRF_NPROC"])
    from repro.launch import multiproc
    multiproc.initialize("127.0.0.1:" + os.environ["PRF_PORT"],
                         nproc, pid, local_device_count=2)
    import json, time
    from repro.core.api import train_prf_multiproc
    from repro.core.types import ForestConfig
    from repro.data.tabular import make_classification
    from repro.launch.multiproc import MultiHostMesh

    K, N, F, B, C, DEPTH = 8, 4096, 32, 16, 3, 6
    x, y = make_classification(
        n_samples=N, n_features=F, n_classes=C, n_informative=8, seed=5
    )
    cfg = ForestConfig(n_trees=K, max_depth=DEPTH, n_bins=B, n_classes=C,
                       feature_mode="all", weighted_voting=False,
                       sample_block=N // 4)
    rt = MultiHostMesh()
    train_prf_multiproc(x, y, cfg, seed=0, runtime=rt)  # warm jit caches
    t0 = time.time()
    train_prf_multiproc(x, y, cfg, seed=0, runtime=rt)
    us = (time.time() - t0) * 1e6
    rt.barrier()
    if pid == 0:
        print("RESULT" + json.dumps(
            {"us_per_call": us, "feed_bytes": int(rt.feed_bytes)}
        ), flush=True)
""")


def run_multiproc(streamed_us):
    """``train_e2e_multiproc``: the 2-process x 2-device training plane
    end to end — each process feeds only its local half of the rows;
    ``single_process_streamed_us`` carries the single-process streamed
    growth time of the same global shape for the trajectory table."""
    port = "12961"
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _MP_CODE],
            env={**os.environ, "PRF_PID": str(i), "PRF_NPROC": "2",
                 "PRF_PORT": port},
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        )
        for i in range(2)
    ]
    outs = [p.communicate(timeout=1800)[0] for p in procs]
    if any(p.returncode != 0 for p in procs):
        return [{"bench": "train_e2e_multiproc",
                 "error": (outs[0] + outs[1])[-500:], "us_per_call": 0.0}]
    line = [ln for ln in outs[0].splitlines() if ln.startswith("RESULT")][-1]
    r = json.loads(line[len("RESULT"):])
    return [{
        "bench": "train_e2e_multiproc",
        "us_per_call": r["us_per_call"],
        "derived": f"{SHAPE},blocks={N_BLOCKS},procs=2x2dev,full_prf_path",
        "feed_mb_per_proc": r["feed_bytes"] / 2**20,
        "single_process_streamed_us": streamed_us,
    }]


def _time(fn, reps=3):
    fn()  # compile / warm the jit caches
    t0 = time.time()
    for _ in range(reps):
        out = fn()
        jax.block_until_ready(jax.tree_util.tree_leaves(out))
    return (time.time() - t0) / reps * 1e6


def _setup():
    x, y = make_classification(
        n_samples=N, n_features=F, n_classes=C, n_informative=8, seed=5
    )
    cfg = ForestConfig(
        n_trees=K, max_depth=DEPTH, n_bins=B, n_classes=C, feature_mode="all"
    )
    xb, _ = bin_dataset(x, cfg.n_bins)
    w = np.asarray(
        bootstrap_counts(jax.random.PRNGKey(0), K, N)
    ).astype(np.float32)
    return xb, y, w, cfg


def run():
    rows = []
    xb, y, w, cfg = _setup()
    xb_dev, y_dev, w_dev = jnp.asarray(xb), jnp.asarray(y), jnp.asarray(w)

    rows.append({
        "bench": "train_e2e_resident",
        "us_per_call": _time(lambda: grow_forest(xb_dev, y_dev, w_dev, cfg)),
        "derived": SHAPE,
    })

    blocks = np.array_split(xb, N_BLOCKS)
    rows.append({
        "bench": "train_e2e_streamed",
        "us_per_call": _time(
            lambda: grow_forest_streamed(blocks, y, w, cfg, prefetch=0)
        ),
        "derived": f"{SHAPE},blocks={N_BLOCKS},fused_route_hist,sync_feed",
    })
    us_streamed = _time(
        lambda: grow_forest_streamed(blocks, y, w, cfg, prefetch=2)
    )
    rows.append({
        "bench": "train_e2e_streamed_prefetch",
        "us_per_call": us_streamed,
        "derived": f"{SHAPE},blocks={N_BLOCKS},fused_route_hist,prefetch=2",
    })

    # Resilience rows: what per-level
    # checkpointing costs over the resident while_loop engine, what a
    # crash resume costs (restore + the remaining levels), and what a
    # 5%-fault feed under bounded retry costs over the clean stream.
    import shutil
    import tempfile

    from repro.checkpoint.checkpoint import CheckpointManager
    from repro.core.forest import grow_forest_checkpointed
    from repro.launch.fault import FaultInjector

    ckpt_dir = tempfile.mkdtemp(prefix="bench_ckpt_")
    try:
        def ckpt_run():
            shutil.rmtree(ckpt_dir, ignore_errors=True)
            return grow_forest_checkpointed(
                xb_dev, y_dev, w_dev, cfg,
                manager=CheckpointManager(ckpt_dir, keep=2, save_interval=1),
            )

        us_ckpt = _time(ckpt_run)

        class _Kill(Exception):
            pass

        def killer(level, _):
            if level == DEPTH // 2:
                raise _Kill

        shutil.rmtree(ckpt_dir, ignore_errors=True)
        try:
            grow_forest_checkpointed(
                xb_dev, y_dev, w_dev, cfg,
                manager=CheckpointManager(ckpt_dir, keep=2, save_interval=1),
                on_level=killer,
            )
        except _Kill:
            pass
        us_resume = _time(lambda: grow_forest_checkpointed(
            xb_dev, y_dev, w_dev, cfg, resume_from=ckpt_dir,
        ))
        rows.append({
            "bench": "train_checkpoint_resume",
            "us_per_call": us_ckpt,
            "derived": f"{SHAPE},ckpt_every_level",
            "resume_from_midpoint_us": us_resume,
            "resident_us": rows[0]["us_per_call"],
        })
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)

    def faulted_run():
        inj = FaultInjector(0.05, seed=3, max_consecutive=2)
        return grow_forest_streamed(
            blocks, y, w, cfg, prefetch=2,
            feeder_opts=dict(fault_hook=inj, max_retries=3, backoff=1e-4),
        )

    rows.append({
        "bench": "train_faulted_feed",
        "us_per_call": _time(faulted_run),
        "derived": f"{SHAPE},blocks={N_BLOCKS},fault_rate=0.05,retries=3",
        "clean_us": us_streamed,
    })

    # Per-block integrity validation (bad_block_policy) on the full
    # streamed train_prf path: the numpy NaN/Inf/label screen runs once
    # per raw block before binning, so its cost is a host-side preamble
    # over the unvalidated run (``unvalidated_us``) — the price of
    # refusing to train on poisoned shards.
    from repro.core.api import train_prf

    x_raw, y_raw = make_classification(
        n_samples=N, n_features=F, n_classes=C, n_informative=8, seed=5
    )
    cfg_stream = dataclasses.replace(cfg, sample_block=N // N_BLOCKS)
    us_unval = _time(lambda: train_prf(
        x_raw, y_raw, cfg_stream, seed=0, bad_block_policy=None
    ))
    us_val = _time(lambda: train_prf(
        x_raw, y_raw, cfg_stream, seed=0, bad_block_policy="raise"
    ))
    rows.append({
        "bench": "train_validated_feed",
        "us_per_call": us_val,
        "derived": f"{SHAPE},blocks={N_BLOCKS},policy=raise",
        "unvalidated_us": us_unval,
        "overhead_frac": us_val / max(us_unval, 1e-9) - 1.0,
    })

    forest = grow_forest(xb_dev, y_dev, w_dev, cfg)
    us_oob_res = _time(
        lambda: oob_accuracy(forest, xb_dev, y_dev, w_dev)
    )
    rows.append({
        "bench": "oob_streamed",
        "us_per_call": _time(
            lambda: oob_accuracy_streamed(forest, blocks, y, w)
        ),
        "derived": f"{SHAPE},blocks={N_BLOCKS}",
        "resident_us": us_oob_res,
    })

    # Sibling-subtraction reuse end to end: the same deep-frontier
    # forest grown with hist_reuse on vs off (bit-identical trees —
    # tests/test_hist_reuse.py). The per-level saving is the halved
    # T_GR scatter (level_hist_reuse_* rows); this row records how much
    # of it survives whole-training amortization on this backend.
    deep_cfg = dataclasses.replace(
        cfg, max_depth=10, max_frontier=512, min_samples_split=4,
    )
    us_reuse_on = _time(lambda: grow_forest(
        xb_dev, y_dev, w_dev,
        dataclasses.replace(deep_cfg, hist_reuse="on")))
    us_reuse_off = _time(lambda: grow_forest(
        xb_dev, y_dev, w_dev,
        dataclasses.replace(deep_cfg, hist_reuse="off")))
    rows.append({
        "bench": "train_e2e_reuse",
        "us_per_call": us_reuse_on,
        "derived": f"{SHAPE.replace(f'depth={DEPTH}', 'depth=10')},S=512",
        "off_us": us_reuse_off,
        "speedup_vs_off": us_reuse_off / max(us_reuse_on, 1e-9),
    })

    # Over-budgeted depth on separable data: trees purify and every
    # frontier dies at ~level 4 of a 16-level budget, so the early-exit
    # while_loop skips ~3/4 of the level work; the fixed-depth run of
    # the *bit-identical* forest is the baseline the saving is measured
    # against. max_frontier bounds S (the default 2**16 frontier would
    # dominate the timing with dead-slot histogram work).
    x2, y2 = make_classification(
        n_samples=N, n_features=F, n_classes=C, n_informative=10,
        class_sep=3.0, label_noise=0.0, seed=5,
    )
    xb2, _ = bin_dataset(x2, B)
    deep = dataclasses.replace(
        cfg, max_depth=16, max_frontier=64, min_samples_split=32,
        early_exit=True,
    )
    fixed = dataclasses.replace(deep, early_exit=False)
    xb2_dev, y2_dev = jnp.asarray(xb2), jnp.asarray(y2)
    us_ee = _time(lambda: grow_forest(xb2_dev, y2_dev, w_dev, deep))
    us_fx = _time(lambda: grow_forest(xb2_dev, y2_dev, w_dev, fixed))
    rows.append({
        "bench": "train_early_exit",
        "us_per_call": us_ee,
        "derived": f"{SHAPE.replace(f'depth={DEPTH}', 'depth=16')},"
                   "S=64,separable",
        "fixed_depth_us": us_fx,
        "speedup_vs_fixed": us_fx / max(us_ee, 1e-9),
    })
    rows.extend(run_multiproc(us_streamed))
    return rows
