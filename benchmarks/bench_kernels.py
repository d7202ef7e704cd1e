"""Kernel micro-benchmarks: Pallas (interpret) vs jnp reference.

On this CPU container interpret-mode timings measure Python emulation,
NOT TPU performance — reported for completeness; correctness sweeps live
in tests/test_kernels.py. The ``level_hist_*`` rows time the T_GR
backend on the histogram shapes training actually builds (multi-tree,
both backends, packed and unpacked); ``level_scores_*`` times the T_NS
split-scoring backends on the same shapes, ``hist_score_fused_*`` the
end-to-end T_GR->T_NS chunk (fused no-HBM-histogram path vs the
two-tensor xla path), ``predict_*`` the Eq. 9/10 weighted-voting
backends on a trained forest, and ``serve_throughput`` the bucketed
serving layer end to end — the series BENCH_kernels.json tracks across
PRs (see PERF.md).
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

from repro.core.forest import chunked_level_scores
from repro.core.gain import level_scores
from repro.core.histograms import level_histograms
from repro.core.types import ForestConfig
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.gain_ratio.ref import histogram_ref
from repro.kernels.ssd_scan.ref import ssd_ref

# The training shape every suite row below uses: a mid-level of
# grow_forest — tc trees, S live frontier slots.
TC, N, F, S, B, C = 4, 2048, 32, 4, 16, 4
SHAPE = f"tc={TC},N={N},F={F},S={S},B={B},C={C}"


def _time(fn, *args, reps=3):
    fn(*args)  # compile
    t0 = time.time()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.time() - t0) / reps * 1e6


def _training_batch(rng):
    xb = jnp.asarray(rng.integers(0, B, (N, F)).astype(np.uint8))
    base = jnp.asarray(np.eye(C, dtype=np.float32)[rng.integers(0, C, N)])
    w = jnp.asarray(rng.integers(0, 4, (TC, N)).astype(np.float32))
    slot = jnp.asarray(rng.integers(-1, S, (TC, N)).astype(np.int32))
    return xb, base, w, slot


def run_level_hist():
    """Training-shaped T_GR benchmark: one level of a tree chunk."""
    rng = np.random.default_rng(0)
    rows = []
    xb, base, w, slot = _training_batch(rng)
    for backend in ("segment_sum", "pallas"):
        for packed in (False, True):
            fn = jax.jit(
                lambda a, b, c, d, _be=backend, _pk=packed: level_histograms(
                    a, b, c, d, n_slots=S, n_bins=B,
                    packed=_pk, backend=_be,
                )
            )
            name = f"level_hist_{backend}" + ("_packed" if packed else "")
            rows.append({
                "bench": name,
                "us_per_call": _time(fn, xb, base, w, slot),
                "derived": SHAPE,
                "backend": backend,
                "packed": packed,
            })
    return rows


def run_level_hist_reuse():
    """Sibling-subtraction T_GR at a deep-forest shape (S=512 frontier
    slots over 2048 samples — the thin-deep-level regime where the
    scatter's output zeroing dominates). ``level_hist_reuse_off`` is
    the full S-slot scatter; ``level_hist_reuse_on`` the packed
    R=S/2-rank scatter the reuse plane runs instead (its headline
    ``speedup_vs_off`` is the level-histogram-phase saving the
    acceptance bar tracks). ``with_expand_us`` adds the
    ``sibling_expand`` reconstruction (gather parent rows, subtract,
    concat) that reuse folds into the scoring-prep step — the honest
    end-of-phase cost of producing the same [k, S, F, B, C] tensor.
    """
    from repro.core.histograms import sibling_expand, sibling_segments

    TCd, Nd, Fd, Sd = 4, 2048, 32, 512
    Rd = Sd // 2
    shape = f"tc={TCd},N={Nd},F={Fd},S={Sd},B={B},C={C}"
    rng = np.random.default_rng(4)
    xb = jnp.asarray(rng.integers(0, B, (Nd, Fd)).astype(np.uint8))
    base = jnp.asarray(np.eye(C, dtype=np.float32)[rng.integers(0, C, Nd)])
    w = jnp.asarray(rng.integers(0, 4, (TCd, Nd)).astype(np.float32))
    slot = jnp.asarray(rng.integers(-1, Sd, (TCd, Nd)).astype(np.int32))
    small_right = jnp.asarray(rng.integers(0, 2, (TCd, Rd)).astype(np.int32))
    parent = jnp.asarray(rng.integers(0, Sd, (TCd, Rd)).astype(np.int32))
    cache_hist = jnp.asarray(
        rng.integers(0, 8, (TCd, Sd, Fd, B, C)).astype(np.float32))
    cache_perm = jnp.tile(jnp.arange(Sd, dtype=jnp.int32)[None], (TCd, 1))

    f_off = jax.jit(lambda a, b, c, d: level_histograms(
        a, b, c, d, n_slots=Sd, n_bins=B, backend="segment_sum"))

    def packed_only(a, b, c, d, sr):
        seg = sibling_segments(d, sr)
        return level_histograms(
            a, b, c, seg, n_slots=Rd, n_bins=B, backend="segment_sum")

    def packed_expand(a, b, c, d, sr, ch, cp, par):
        h = packed_only(a, b, c, d, sr)
        return sibling_expand(h, ch, cp, par, Sd)

    f_on = jax.jit(packed_only)
    f_exp = jax.jit(packed_expand)
    us_off = _time(f_off, xb, base, w, slot)
    us_on = _time(f_on, xb, base, w, slot, small_right)
    us_exp = _time(
        f_exp, xb, base, w, slot, small_right, cache_hist, cache_perm, parent)
    return [
        {"bench": "level_hist_reuse_off", "us_per_call": us_off,
         "derived": shape, "backend": "segment_sum"},
        {"bench": "level_hist_reuse_on", "us_per_call": us_on,
         "derived": f"{shape},R={Rd}", "backend": "segment_sum",
         "speedup_vs_off": us_off / max(us_on, 1e-9),
         "with_expand_us": us_exp,
         "with_expand_speedup": us_off / max(us_exp, 1e-9)},
    ]


def run_comm_reuse():
    """Mesh psum volume with sibling-subtraction reuse on vs off: lower
    the distributed trainer under each, parse per-device collective
    bytes from the post-SPMD HLO (deterministic — no timing). The
    per-level histogram combine is the dominant collective at this
    shape, so ``on`` must move about half of ``off``'s bytes — CI
    asserts the ratio."""
    code = textwrap.dedent("""
        import os
        os.environ["JAX_PLATFORMS"] = "cpu"  # a CPU drill: never the parent's chip
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import dataclasses, json
        import jax, jax.numpy as jnp
        from repro.core import ForestConfig
        from repro.core.distributed import make_prf_train_fn
        from repro.launch.mesh import make_mesh
        from repro.roofline.analysis import analyze_hlo_text

        N, F, C = 1 << 12, 128, 4
        cfg0 = ForestConfig(n_trees=8, max_depth=5, n_bins=16, n_classes=C,
                            max_frontier=32, tree_chunk=4)
        mesh = make_mesh((2, 4), ("data", "model"))
        out = {}
        for mode in ("off", "on"):
            cfg = dataclasses.replace(cfg0, hist_reuse=mode)
            fn, _ = make_prf_train_fn(cfg, mesh)
            comp = fn.lower(
                jax.ShapeDtypeStruct((N, F), jnp.uint8),
                jax.ShapeDtypeStruct((N,), jnp.int32),
                jax.ShapeDtypeStruct((2,), jnp.uint32),
            ).compile()
            a = analyze_hlo_text(comp.as_text())
            out[mode] = a["collective_bytes"] / 2**20
        print("RESULT" + json.dumps(out))
    """)
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=1800)
    if p.returncode != 0:
        return [{"bench": "comm_psum_reuse", "error": p.stderr[-500:],
                 "us_per_call": 0.0}]
    line = [l for l in p.stdout.splitlines() if l.startswith("RESULT")][-1]
    mb = json.loads(line[len("RESULT"):])
    return [{
        "bench": "comm_psum_reuse",
        "us_per_call": 0.0,
        "derived": "N=4096,F=128,k=8,depth=5,S=32,mesh=2x4,psum",
        "collective_mb_off": mb["off"],
        "collective_mb_on": mb["on"],
        "on_over_off": mb["on"] / max(mb["off"], 1e-9),
    }]


def run_comm_multiproc():
    """``comm_multiproc``: cross-host collective volume of the
    multi-process training plane (deterministic HLO byte counts, no
    timing). Two coordinator-connected processes x 2 devices AOT-lower
    the two collectives every level pays on that plane — the data-axis
    histogram combine and the int64-limbed verdict/barrier psum
    (``MultiHostMesh.psum_hosts``) — and parse per-device bytes from the
    post-SPMD HLO."""
    code = textwrap.dedent("""
        import os
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = ""
        pid = int(os.environ["PRF_PID"])
        nproc = int(os.environ["PRF_NPROC"])
        from repro.launch import multiproc
        multiproc.initialize("127.0.0.1:" + os.environ["PRF_PORT"],
                             nproc, pid, local_device_count=2)
        import json
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.launch.multiproc import MultiHostMesh
        from repro.roofline.analysis import analyze_hlo_text

        rt = MultiHostMesh()
        K, S, F, B, C = 8, 32, 32, 16, 3
        D = rt.n_data_shards
        # The per-level histogram combine: [D, k, S, F, B, C] carries
        # sharded over the data axis, summed across hosts.
        hist = jax.ShapeDtypeStruct((D, K, S, F, B, C), jnp.float32)
        fn = jax.jit(jax.shard_map(
            lambda h: jax.lax.psum(h[0], "data"),
            mesh=rt.mesh,
            in_specs=(P("data", None, None, "model"),),
            out_specs=P(None, None, "model"),
            check_vma=False,
        ))
        a_hist = analyze_hlo_text(fn.lower(hist).compile().as_text())
        # The limbed int64 union (validation verdicts, barriers):
        # [D, n, 3] int32 over the same axis.
        vec = jax.ShapeDtypeStruct((D, 1024, 3), jnp.int32)
        fn2 = jax.jit(jax.shard_map(
            lambda x: jax.lax.psum(x[0], "data"),
            mesh=rt.mesh, in_specs=(P("data",),), out_specs=P(),
            check_vma=False,
        ))
        a_vec = analyze_hlo_text(fn2.lower(vec).compile().as_text())
        rt.barrier()
        if pid == 0:
            print("RESULT" + json.dumps({
                "hist_mb": a_hist["collective_bytes"] / 2**20,
                "hist_ops": {k: int(v["count"])
                             for k, v in a_hist["collectives"].items()},
                "verdict_kb": a_vec["collective_bytes"] / 2**10,
            }), flush=True)
    """)
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", code],
            env={**os.environ, "PRF_PID": str(i), "PRF_NPROC": "2",
                 "PRF_PORT": "12963"},
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        )
        for i in range(2)
    ]
    outs = [p.communicate(timeout=1800)[0] for p in procs]
    if any(p.returncode != 0 for p in procs):
        return [{"bench": "comm_multiproc",
                 "error": (outs[0] + outs[1])[-500:], "us_per_call": 0.0}]
    line = [ln for ln in outs[0].splitlines() if ln.startswith("RESULT")][-1]
    r = json.loads(line[len("RESULT"):])
    return [{
        "bench": "comm_multiproc",
        "us_per_call": 0.0,
        "derived": "k=8,S=32,F=32,B=16,C=3,procs=2x2dev,psum",
        "hist_collective_mb_per_device": r["hist_mb"],
        "hist_collective_ops": r["hist_ops"],
        "verdict_collective_kb_per_device": r["verdict_kb"],
    }]


def run_level_scores():
    """T_NS split-scoring backends on a pre-built training-shaped
    histogram, plus the end-to-end T_GR->T_NS chunk: the fused
    hist-kernel -> score-kernel path (no HBM histogram) vs the
    two-tensor xla path."""
    rng = np.random.default_rng(1)
    rows = []
    hist = jnp.asarray(rng.integers(0, 4, (TC, S, F, B, C)).astype(np.float32))
    mask = jnp.ones((TC, F), jnp.bool_)
    for be in ("xla", "pallas"):
        fn = jax.jit(
            lambda h, m, _be=be: level_scores(h, m, backend=_be)
        )
        rows.append({
            "bench": f"level_scores_{be}",
            "us_per_call": _time(fn, hist, mask),
            "derived": SHAPE,
            "backend": be,
        })

    xb, base, w, slot = _training_batch(rng)
    cfg0 = ForestConfig(
        n_trees=TC, max_depth=2, n_bins=B, n_classes=C,
        max_frontier=S, feature_mode="all",
    )
    for be in ("xla", "pallas"):
        cfg = dataclasses.replace(cfg0, split_backend=be)
        fn = jax.jit(
            lambda a, b, c, d, _cfg=cfg: chunked_level_scores(
                a, b, c, d, None, _cfg
            )
        )
        rows.append({
            "bench": f"hist_score_fused_{be}",
            "us_per_call": _time(fn, xb, base, w, slot),
            "derived": SHAPE,
            "backend": be,
        })
    return rows


def run_predict():
    """Prediction backends + serving throughput on a trained forest.

    ``predict_xla`` routes the full [k, N, C] per-tree tensor through
    HBM before voting; ``predict_pallas`` is the fused traversal+voting
    kernel (interpret-mode emulation off-TPU). ``serve_throughput``
    times PRFService.predict — binning, bucketing, padding and the
    jit'd bucket forward pass — on a full bucket of raw rows.
    """
    from repro.core.api import train_prf
    from repro.core.binning import apply_bins
    from repro.core.voting import predict
    from repro.data.tabular import make_classification
    from repro.serving import PRFService

    rows = []
    k, depth = 16, 6
    x, y = make_classification(n_samples=N, n_features=F, n_classes=C, seed=3)
    cfg = ForestConfig(
        n_trees=k, max_depth=depth, n_bins=B, n_classes=C, feature_mode="all",
    )
    model = train_prf(x, y, cfg, seed=0)
    xb = apply_bins(jnp.asarray(x), jnp.asarray(model.bin_edges))
    shape = f"k={k},depth={depth},N={N},F={F},B={B},C={C}"
    for be in ("xla", "pallas"):
        fn = jax.jit(lambda a, _be=be: predict(model.forest, a, backend=_be))
        rows.append({
            "bench": f"predict_{be}",
            "us_per_call": _time(fn, xb),
            "derived": shape,
            "backend": be,
        })

    svc = PRFService(model, max_batch=1024, min_bucket=8)
    batch = x[:1024]
    us = _time(lambda: svc.predict(batch))
    rows.append({
        "bench": "serve_throughput",
        "us_per_call": us,
        "derived": f"batch=1024,{shape}",
        "rows_per_s": 1024 / (us / 1e6),
    })

    # Resilience rows. serve_overload floods
    # submit() against a 128-row admission cap, draining every 24
    # requests — admitted requests report submit->resolve latency,
    # overflow is shed with typed errors, never queued. serve_hotswap
    # times a ModelRegistry publish under 32 in-flight futures; the
    # old version drains on retirement, so dropped_futures must be 0.
    from repro.serving import ModelRegistry, ServiceError

    ovl = PRFService(model, max_batch=1024, min_bucket=8, max_queue_rows=128)
    ovl.predict(batch[:8])  # warm the small-bucket forward pass
    lat, pending, shed, total = [], [], 0, 288
    for i in range(total):
        t0 = time.perf_counter()
        try:
            j = (i * 8) % (N - 8)
            pending.append((ovl.submit(x[j:j + 8]), t0))
        except ServiceError:
            shed += 1
        if i % 24 == 23:
            ovl.drain()
            now = time.perf_counter()
            lat += [now - t for _, t in pending]
            pending = []
    ovl.drain()
    now = time.perf_counter()
    lat += [now - t for _, t in pending]
    lat_us = sorted(v * 1e6 for v in lat)
    rows.append({
        "bench": "serve_overload",
        "us_per_call": lat_us[len(lat_us) // 2],
        "derived": f"req=8rows,queue_cap=128rows,drain_every=24,{shape}",
        "p99_us": lat_us[min(len(lat_us) - 1, int(len(lat_us) * 0.99))],
        "shed_fraction": shed / total,
        "admitted": len(lat_us),
    })

    reg = ModelRegistry(max_batch=1024, min_bucket=8)
    reg.publish(model)
    reg.predict(batch[:8])  # warm v1 so the retirement drain is pure serving
    futs = [reg.submit(x[j * 8:(j + 1) * 8]) for j in range(32)]
    t0 = time.perf_counter()
    reg.publish(model)  # hot-swap: pointer flip, then v1 drains its queue
    swap_us = (time.perf_counter() - t0) * 1e6
    rows.append({
        "bench": "serve_hotswap",
        "us_per_call": swap_us,
        "derived": f"inflight=32x8rows,{shape}",
        "dropped_futures": sum(1 for f in futs if not f.done()),
        "swapped_to_version": reg.version,
    })

    # serve_degraded: traffic during a live-version brownout. v2 is
    # published and its breaker forced open; predict falls back to the
    # retired-but-healthy v1 (us_per_call = that stale-fallback path).
    # Before the brownout, one deadline'd request goes stale in the
    # queue and one client overruns its token bucket — both rejected
    # typed and counted, every admitted future settled (dropped must
    # stay 0). The clock is injected: deadline/refill time is virtual.
    from repro.serving import DeadlineExceeded, RateLimited, RateLimiter

    tick = [0.0]
    rl = RateLimiter(rate=1.0, burst=64, clock=lambda: tick[0])
    reg2 = ModelRegistry(
        max_batch=1024, min_bucket=8, rate_limiter=rl,
        clock=lambda: tick[0],
    )
    reg2.publish(model)
    reg2.predict(batch[:8])             # warm v1 (the future fallback)
    reg2.publish(model)
    reg2.predict(batch[:8])             # warm v2 (the live version)
    futs2 = []
    stale = reg2.submit(x[:8], client="lat", deadline=1.0)
    futs2.append(stale)
    tick[0] = 2.0                       # the deadline'd request goes stale
    granted = rejected = 0
    for j in range(9):                  # 9 x 8 rows vs a 64-token bucket
        try:
            futs2.append(reg2.submit(x[j * 8:(j + 1) * 8], client="hog"))
            granted += 1
        except RateLimited:
            rejected += 1
    reg2.drain()                        # settles stale + granted futures
    assert isinstance(stale.exception(), DeadlineExceeded)
    for _ in range(5):
        reg2.service.breaker.record_failure()   # brownout: v2 opens
    us_fb = _time(lambda: reg2.predict(batch[:8]))
    h = reg2.health()
    rows.append({
        "bench": "serve_degraded",
        "us_per_call": us_fb,
        "derived": f"fallback=v1,live_breaker=open,req=8rows,{shape}",
        "fallback_served": h["fallback_served"],
        "deadline_exceeded": h["live"]["deadline_exceeded"],
        "rate_limited": h["live"]["rate_limited"],
        "rate_limit_granted": granted,
        "rate_limit_rejected": rejected,
        "dropped_futures": sum(1 for f in futs2 if not f.done()),
        "live_us": us,                  # healthy serve_throughput path
    })
    return rows


def run_binning():
    """Bin-edge fitting: full-pass np.quantile vs the blocked sketch.

    Tracks both wall time and **peak host allocation** (tracemalloc —
    numpy registers its buffers with it): the exact path copies + sorts
    the whole [N, F] source, the blocked path is O(block) + O(F * sketch)
    no matter how large N grows. Same seed -> the blocked row also
    reports its max edge deviation from exact.
    """
    import tracemalloc

    from repro.core.binning import fit_bins, fit_bins_blocked

    n_rows, n_feat, n_bins, block = 120_000, 64, 64, 8_192
    x = np.random.default_rng(7).standard_normal(
        (n_rows, n_feat), dtype=np.float32)
    derived = f"N={n_rows},F={n_feat},B={n_bins}"

    def _metered(fn):
        tracemalloc.start()
        t0 = time.time()
        out = fn()
        us = (time.time() - t0) * 1e6
        _, peak = tracemalloc.get_traced_memory()
        tracemalloc.stop()
        return out, us, peak

    exact, us_exact, peak_exact = _metered(lambda: fit_bins(x, n_bins))
    blocks = [x[i:i + block] for i in range(0, n_rows, block)]
    blocked, us_blocked, peak_blocked = _metered(
        lambda: fit_bins_blocked(blocks, n_bins))
    return [
        {"bench": "fit_bins_exact", "us_per_call": us_exact,
         "peak_bytes": int(peak_exact), "derived": derived},
        {"bench": "fit_bins_blocked", "us_per_call": us_blocked,
         "peak_bytes": int(peak_blocked),
         "max_edge_err": float(np.max(np.abs(blocked - exact))),
         "derived": f"{derived},block={block}"},
    ]


def run():
    rng = np.random.default_rng(0)
    rows = (
        run_level_hist() + run_level_hist_reuse() + run_comm_reuse()
        + run_comm_multiproc() + run_level_scores() + run_predict()
        + run_binning()
    )

    N, F, S, B, C = 2048, 128, 4, 16, 4
    xb = jnp.asarray(rng.integers(0, B, (N, F)).astype(np.int32))
    w = rng.random(N).astype(np.float32)
    y = rng.integers(0, C, N)
    wch = jnp.asarray(w[:, None] * np.eye(C, dtype=np.float32)[y])
    slot = jnp.asarray(rng.integers(0, S, N).astype(np.int32))
    f = jax.jit(lambda a, b, c: histogram_ref(a, b, c, n_slots=S, n_bins=B))
    rows.append({"bench": "kernel_gain_ratio_ref",
                 "us_per_call": _time(f, xb, wch, slot),
                 "derived": f"N={N},F={F}"})

    q = jnp.asarray(rng.standard_normal((8, 512, 64)).astype(np.float32))
    f = jax.jit(lambda a: attention_ref(a, a, a, causal=True))
    rows.append({"bench": "kernel_attention_ref", "us_per_call": _time(f, q),
                 "derived": "BH=8,L=512,D=64"})

    x = jnp.asarray(rng.standard_normal((4, 512, 64)).astype(np.float32))
    loga = jnp.asarray(-np.abs(rng.standard_normal((4, 512))).astype(np.float32))
    b = jnp.asarray(rng.standard_normal((4, 512, 32)).astype(np.float32) * 0.3)
    f = jax.jit(lambda x_, l_, b_: ssd_ref(x_, l_, b_, b_)[0])
    rows.append({"bench": "kernel_ssd_ref", "us_per_call": _time(f, x, loga, b),
                 "derived": "BH=4,L=512,P=64,N=32"})
    return rows
