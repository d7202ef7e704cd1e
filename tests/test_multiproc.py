"""Multi-process training plane drills (launch.multiproc).

Every drill runs 2 coordinator-connected processes x 2 CPU devices each
in subprocesses (jax.distributed must own the process from its first jax
import, so none of this can run in the pytest process), and compares
against single-process references:

* bitwise forest/edge parity: 2x2 multi-process == 1-process runtime
  mesh == LocalPlane ``train_prf``, clean and dirty (sanitize /
  quarantine), and with sibling-subtraction ``hist_reuse="on"``;
* kill-and-resume through the multi-process checkpoint protocol lands
  bit-identical to an uninterrupted run;
* resuming across a *changed* process count raises
  ``CheckpointTopologyError`` in both directions (2->1 and 1->2);
* per-process host memory for the streamed fit+growth stays bounded by
  the local shard (tracemalloc peak < raw_bytes / (2 * n_data_shards)).
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")

WORKER = textwrap.dedent("""
    import json, os, sys, traceback

    SRC = sys.argv[1]
    role = sys.argv[2]            # single | mesh1 | mp
    pid = int(sys.argv[3])
    nproc = int(sys.argv[4])
    port = int(sys.argv[5])
    scenario = sys.argv[6]
    workdir = sys.argv[7]

    sys.path.insert(0, SRC)
    os.environ["JAX_PLATFORMS"] = "cpu"
    if role == "single":
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    elif role == "mesh1":
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    else:
        os.environ["XLA_FLAGS"] = ""
        from repro.launch import multiproc
        multiproc.initialize(
            f"127.0.0.1:{port}", nproc, pid, local_device_count=2
        )

    import hashlib
    import numpy as np
    from repro.core.types import ForestConfig

    def model_hash(model):
        import jax
        h = hashlib.sha256()
        for leaf in jax.tree_util.tree_leaves(model.forest):
            h.update(np.asarray(leaf).tobytes())
        h.update(np.asarray(model.bin_edges).tobytes())
        return h.hexdigest()

    def make_data(n, f, dirty=False, nb=100):
        rng = np.random.default_rng(7)
        x = rng.normal(size=(n, f)).astype(np.float32)
        y = ((x[:, 0] + 0.5 * x[:, 1] > 0).astype(np.int32)
             + (x[:, 2] > 0.5).astype(np.int32))
        if dirty:
            x[nb + 3, 2] = np.nan          # block 1: non-finite cells
            x[nb + 7, 5] = np.inf
            y[2 * nb + 1] = 99             # block 2: out-of-range label
        return x, y

    def base_cfg(**over):
        kw = dict(n_trees=5, max_depth=4, n_bins=8, n_classes=3,
                  feature_mode="importance", weighted_voting=True,
                  sample_block=100)
        kw.update(over)
        return ForestConfig(**kw)

    out = {}
    try:
        kw = {}
        if scenario == "clean":
            x, y = make_data(250, 13)
            cfg = base_cfg()
        elif scenario == "reuse":
            x, y = make_data(250, 13)
            cfg = base_cfg(hist_reuse="on")
        elif scenario in ("sanitize", "quarantine"):
            x, y = make_data(250, 13, dirty=True)
            cfg = base_cfg()
            kw = {"bad_block_policy": scenario}
        elif scenario in ("ckpt_crash", "ckpt_resume", "topo"):
            x, y = make_data(250, 13)
            cfg = base_cfg()
            d = os.path.join(workdir, "ckpt")
            if scenario == "ckpt_crash":
                def boom(level, _):
                    if level >= 2:
                        raise RuntimeError("simulated crash")
                kw = {"checkpoint_dir": d, "checkpoint_every": 1,
                      "on_level": boom}
            else:
                kw = {"resume_from": d}
        elif scenario == "mem":
            n, f = 160000, 128
            x = np.memmap(os.path.join(workdir, "mem.f64"),
                          dtype=np.float64, mode="r", shape=(n, f))
            y = np.load(os.path.join(workdir, "mem.y.npy"))
            cfg = ForestConfig(n_trees=2, max_depth=3, n_bins=16,
                               n_classes=2, weighted_voting=False,
                               sample_block=10000)
            kw = {"bad_block_policy": None, "sketch_max_size": 64}

        if role == "single":
            from repro.core.api import train_prf
            model = train_prf(x, y, cfg, seed=3, **kw)
        else:
            from repro.core.api import train_prf_multiproc
            if scenario == "mem":
                import tracemalloc
                # First run warms the jit caches: tracing/compile
                # allocations are one-time and shape-dependent, not
                # data-plane memory. The traced second run measures
                # what the streamed fit+growth actually holds per
                # process at steady state.
                train_prf_multiproc(x, y, cfg, seed=3, **kw)
                import gc
                gc.collect()
                tracemalloc.start()
                model = train_prf_multiproc(x, y, cfg, seed=3, **kw)
                out["peak"] = int(tracemalloc.get_traced_memory()[1])
                out["raw"] = int(n) * int(f) * 8
            else:
                model = train_prf_multiproc(x, y, cfg, seed=3, **kw)
        out["hash"] = model_hash(model)
        if model.quarantine is not None:
            out["counters"] = {k: int(v)
                               for k, v in model.quarantine.counters().items()}
            out["quarantined"] = [int(i)
                                  for i in model.quarantine.quarantined]
    except BaseException as e:
        out["error"] = type(e).__name__
        out["message"] = str(e)[:500]
        out["trace"] = traceback.format_exc()[-2000:]
    print("RESULT " + json.dumps(out), flush=True)
""")

_PORT = [13801]


@pytest.fixture(scope="session")
def worker_path(tmp_path_factory):
    p = tmp_path_factory.mktemp("mp") / "worker.py"
    p.write_text(WORKER)
    return str(p)


def _parse(out, rc, who):
    for ln in reversed(out.splitlines()):
        if ln.startswith("RESULT "):
            return json.loads(ln[len("RESULT "):])
    raise AssertionError(f"{who} produced no RESULT (rc={rc}):\n{out[-3000:]}")


def _run(worker, role, scenario, workdir, nproc=2, timeout=600):
    """Launch one drill; returns a list of per-process RESULT dicts."""
    if role == "mp":
        _PORT[0] += 1
        port = _PORT[0]
        procs = [
            subprocess.Popen(
                [sys.executable, worker, SRC, "mp", str(i), str(nproc),
                 str(port), scenario, workdir],
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            )
            for i in range(nproc)
        ]
        outs = [p.communicate(timeout=timeout)[0] for p in procs]
        return [
            _parse(out, p.returncode, f"mp proc {i}")
            for i, (p, out) in enumerate(zip(procs, outs))
        ]
    p = subprocess.run(
        [sys.executable, worker, SRC, role, "0", "1", "0", scenario, workdir],
        capture_output=True, text=True, timeout=timeout,
    )
    return [_parse(p.stdout + p.stderr, p.returncode, role)]


def _ok(r):
    assert "error" not in r, f"{r.get('error')}: {r.get('message')}\n{r.get('trace', '')}"
    return r


@pytest.fixture(scope="session")
def clean_single_hash(worker_path, tmp_path_factory):
    wd = str(tmp_path_factory.mktemp("clean_ref"))
    return _ok(_run(worker_path, "single", "clean", wd)[0])["hash"]


def test_multiproc_bitwise_parity_clean(worker_path, tmp_path, clean_single_hash):
    """2 procs x 2 devices == 1-process runtime mesh == LocalPlane."""
    mesh1 = _ok(_run(worker_path, "mesh1", "clean", str(tmp_path))[0])
    mps = [_ok(r) for r in _run(worker_path, "mp", "clean", str(tmp_path))]
    assert mesh1["hash"] == clean_single_hash
    assert [r["hash"] for r in mps] == [clean_single_hash] * 2


def test_multiproc_parity_hist_reuse(worker_path, tmp_path):
    """Sibling-subtraction reuse stays bitwise on the multi-process plane."""
    ref = _ok(_run(worker_path, "single", "reuse", str(tmp_path))[0])
    mps = [_ok(r) for r in _run(worker_path, "mp", "reuse", str(tmp_path))]
    assert [r["hash"] for r in mps] == [ref["hash"]] * 2


@pytest.mark.parametrize("policy", ["sanitize", "quarantine"])
def test_multiproc_parity_dirty(worker_path, tmp_path, policy):
    """The union-reduced validator reaches the single-host verdicts and
    the downstream model bitwise."""
    ref = _ok(_run(worker_path, "single", policy, str(tmp_path))[0])
    mps = [_ok(r) for r in _run(worker_path, "mp", policy, str(tmp_path))]
    for r in mps:
        assert r["hash"] == ref["hash"]
        assert r["counters"] == ref["counters"]
        assert r["quarantined"] == ref["quarantined"]


def test_multiproc_checkpoint_kill_and_resume(worker_path, tmp_path,
                                              clean_single_hash):
    """Both processes die after level 2; a fresh 2-process fleet resumes
    from the multi-process checkpoint and lands bit-identical."""
    crash = _run(worker_path, "mp", "ckpt_crash", str(tmp_path))
    for r in crash:
        assert r.get("error") == "RuntimeError", r
        assert "simulated crash" in r.get("message", "")
    steps = sorted(os.listdir(tmp_path / "ckpt"))
    assert any(s.startswith("step_") for s in steps), steps
    resumed = [_ok(r) for r in _run(worker_path, "mp", "ckpt_resume",
                                    str(tmp_path))]
    assert [r["hash"] for r in resumed] == [clean_single_hash] * 2


def test_multiproc_checkpoint_topology_change(worker_path, tmp_path_factory):
    """Resume across a changed process count is a typed refusal — never a
    silently wrong forest — in both directions."""
    # 2-process checkpoint -> 1-process resume
    wd2 = str(tmp_path_factory.mktemp("topo2to1"))
    crash = _run(worker_path, "mp", "ckpt_crash", wd2)
    assert all(r.get("error") == "RuntimeError" for r in crash), crash
    r = _run(worker_path, "mesh1", "topo", wd2)[0]
    assert r.get("error") == "CheckpointTopologyError", r

    # 1-process checkpoint -> 2-process resume
    wd1 = str(tmp_path_factory.mktemp("topo1to2"))
    crash = _run(worker_path, "mesh1", "ckpt_crash", wd1)
    assert crash[0].get("error") == "RuntimeError", crash
    rs = _run(worker_path, "mp", "topo", wd1)
    assert all(r.get("error") == "CheckpointTopologyError" for r in rs), rs


def test_multiproc_memory_bounded_by_local_shard(worker_path,
                                                 tmp_path_factory):
    """Streamed fit+growth peak host memory per process stays under
    raw_bytes / (2 * n_data_shards) on a memmap source — each process
    only ever materializes its own slice."""
    wd = tmp_path_factory.mktemp("mem")
    n, f = 160000, 128
    rng = np.random.default_rng(11)
    mm = np.memmap(wd / "mem.f64", dtype=np.float64, mode="w+", shape=(n, f))
    for o in range(0, n, 10000):
        mm[o:o + 10000] = rng.normal(size=(10000, f))
    mm.flush()
    del mm
    np.save(wd / "mem.y.npy",
            rng.integers(0, 2, size=n).astype(np.int32))
    results = [_ok(r) for r in _run(worker_path, "mp", "mem", str(wd))]
    assert len({r["hash"] for r in results}) == 1
    bound = results[0]["raw"] / (2 * 4)            # D = 4 data shards
    for i, r in enumerate(results):
        assert r["peak"] < bound, (
            f"proc {i} peak {r['peak'] / 2**20:.1f} MiB >= bound "
            f"{bound / 2**20:.1f} MiB"
        )
