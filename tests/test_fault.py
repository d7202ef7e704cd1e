"""Fault-tolerance parity suite (ISSUE 6 tentpole).

The resilience contract: crashes at ANY level boundary, retried feed
failures, and killed feeder threads must never change the model.

* **Kill-and-resume, every boundary** — growth is killed after each
  completed level's checkpoint and resumed from disk; the resumed
  forest, tree weights, and predictions must be bit-identical to an
  uninterrupted run, on {local, mesh} x {resident, streamed} (the mesh
  half runs in a subprocess so the 8-device XLA flag never leaks).
* **Retrying block feeds** — a deterministic ``FaultInjector`` makes
  ``BlockFeeder`` device puts fail transiently; bounded retry +
  backoff must absorb every injected fault bit-invisibly (hypothesis
  property over rates/seeds), exhaustion must surface ``FeedError``
  with the feeder thread joined, and early close / context-manager
  exit must never leak the thread.
"""
import dataclasses
import subprocess
import sys
import textwrap
import threading
import time

import jax
import numpy as np
import pytest

from repro.core import ForestConfig, train_prf
from repro.data.pipeline import BlockFeeder, FeedError
from repro.launch.fault import FaultInjector, SimulatedFailure
from repro.data.tabular import make_classification

FOREST_ARRAYS = (
    "feature", "threshold", "left_child", "class_counts", "value",
    "tree_weight",
)


def _assert_models_equal(a, b, msg=""):
    for n in FOREST_ARRAYS:
        np.testing.assert_array_equal(
            np.asarray(getattr(a.forest, n)), np.asarray(getattr(b.forest, n)),
            err_msg=f"{n} {msg}",
        )


class _Kill(Exception):
    """The simulated crash: raised from on_level AFTER the level's
    checkpoint is durable — a crash at the level boundary."""


@pytest.fixture(scope="module")
def fault_case():
    x, y = make_classification(n_samples=600, n_features=13, n_classes=3, seed=3)
    cfg = ForestConfig(
        n_trees=6, max_depth=4, n_bins=16, n_classes=3, feature_mode="all"
    )
    return x, y, cfg


@pytest.fixture(scope="module")
def baseline(fault_case):
    x, y, cfg = fault_case
    return train_prf(x, y, cfg, seed=0)


# ---------------------------------------------------------------------------
# Kill-and-resume parity: local planes, every level boundary
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("streamed", [False, True], ids=["resident", "streamed"])
def test_resume_after_crash_bit_identical_local(
    tmp_path, fault_case, baseline, streamed
):
    x, y, cfg = fault_case
    if streamed:
        cfg = dataclasses.replace(cfg, sample_block=170)
    for kill_at in range(1, cfg.max_depth):
        d = str(tmp_path / f"{'st' if streamed else 'rs'}{kill_at}")

        def boom(level, _):
            if level == kill_at:
                raise _Kill

        with pytest.raises(_Kill):
            train_prf(x, y, cfg, seed=0, checkpoint_dir=d, on_level=boom)

        resumed_levels = []
        m = train_prf(
            x, y, cfg, seed=0, checkpoint_dir=d, resume_from=d,
            on_level=lambda level, _: resumed_levels.append(level),
        )
        # The resumed run really starts AFTER the crash level — it must
        # not silently regrow from scratch.
        assert min(resumed_levels) == kill_at + 1, resumed_levels
        _assert_models_equal(m, baseline, f"kill@{kill_at} streamed={streamed}")
        np.testing.assert_array_equal(m.predict(x), baseline.predict(x))


def test_resume_from_empty_dir_is_fresh_start(tmp_path, fault_case, baseline):
    """The ElasticRunner convention: an empty resume directory means
    'no progress yet' — train from scratch, don't raise."""
    x, y, cfg = fault_case
    m = train_prf(x, y, cfg, seed=0, resume_from=str(tmp_path / "nothing"))
    _assert_models_equal(m, baseline, "empty resume dir")


def test_checkpoint_every_gates_saves(tmp_path, fault_case):
    """checkpoint_every=2 writes only even-level checkpoints; resume
    from the latest one still converges to the same model."""
    from repro.checkpoint.checkpoint import latest_step

    x, y, cfg = fault_case
    d = str(tmp_path / "every2")
    base = train_prf(x, y, cfg, seed=0, checkpoint_dir=d, checkpoint_every=2)
    assert latest_step(d) == 4
    m = train_prf(x, y, cfg, seed=0, resume_from=d)
    _assert_models_equal(m, base, "resume from every-2 checkpoint")


# ---------------------------------------------------------------------------
# Kill-and-resume parity: mesh planes (subprocess, 8 host devices)
# ---------------------------------------------------------------------------


def test_resume_after_crash_bit_identical_mesh():
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import numpy as np, jax, jax.numpy as jnp, tempfile
        from repro.core import ForestConfig
        from repro.core.binning import bin_dataset
        from repro.core.distributed import (
            grow_forest_streamed, grow_sharded_checkpointed,
        )
        from repro.core.dsi import bootstrap_counts
        from repro.core.forest import grow_forest
        from repro.checkpoint.checkpoint import CheckpointManager
        from repro.data.tabular import make_classification
        from repro.launch.mesh import make_mesh

        x, y = make_classification(n_samples=640, n_features=16, n_classes=3,
                                   seed=2)
        cfg = ForestConfig(n_trees=6, max_depth=4, n_bins=16, n_classes=3,
                           feature_mode="all").resolved(16)
        xb, _ = bin_dataset(x, cfg.n_bins)
        w = np.asarray(bootstrap_counts(jax.random.PRNGKey(1), cfg.n_trees,
                                        xb.shape[0])).astype(np.float32)
        y_np = np.asarray(y)
        mesh = make_mesh((4, 2), ("data", "model"))
        local = grow_forest(jnp.asarray(xb), jnp.asarray(y), jnp.asarray(w), cfg)
        ARRS = ("feature", "threshold", "left_child", "class_counts", "value")

        class Kill(Exception):
            pass

        def drill(grow, tag):
            for kill_at in (1, 3):
                d = tempfile.mkdtemp()

                def boom(level, _):
                    if level == kill_at:
                        raise Kill

                try:
                    grow(manager=CheckpointManager(d, keep=3, save_interval=1),
                         resume_from=None, on_level=boom)
                    raise AssertionError("kill did not fire")
                except Kill:
                    pass
                resumed = []
                f = grow(manager=None, resume_from=d,
                         on_level=lambda level, _: resumed.append(level))
                assert min(resumed) == kill_at + 1, (tag, kill_at, resumed)
                for n in ARRS:
                    np.testing.assert_array_equal(
                        np.asarray(getattr(f, n)),
                        np.asarray(getattr(local, n)),
                        err_msg=f"{n} {tag} kill@{kill_at}")

        drill(lambda **kw: grow_sharded_checkpointed(
            xb, y_np, w, cfg, mesh, **kw), "mesh-resident")
        cfgs = ForestConfig(n_trees=6, max_depth=4, n_bins=16, n_classes=3,
                            feature_mode="all", sample_block=170).resolved(16)
        drill(lambda **kw: grow_forest_streamed(
            xb, y_np, w, cfgs, mesh=mesh, **kw), "mesh-streamed")
        print("MESH_RESUME_OK")
    """)
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=900,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "MESH_RESUME_OK" in out.stdout


# ---------------------------------------------------------------------------
# Retrying block feeds
# ---------------------------------------------------------------------------


def test_fault_injector_is_deterministic_and_bounded():
    a = FaultInjector(0.5, seed=9, max_consecutive=2)
    b = FaultInjector(0.5, seed=9, max_consecutive=2)
    for _ in range(200):
        ra = rb = None
        try:
            a("s")
        except SimulatedFailure as e:
            ra = str(e)
        try:
            b("s")
        except SimulatedFailure as e:
            rb = str(e)
        assert ra == rb
    assert a.injected > 0
    # The streak cap: never more than max_consecutive faults in a row,
    # so a feeder with max_retries > max_consecutive ALWAYS progresses.
    c = FaultInjector(1.0, seed=0, max_consecutive=2)
    streak, worst = 0, 0
    for _ in range(100):
        try:
            c("s")
            streak = 0
        except SimulatedFailure:
            streak += 1
            worst = max(worst, streak)
    assert worst == 2


def test_feeder_retries_transient_faults(fault_case, baseline):
    """Injected feed failures under bounded retry never change the
    trained model — and the retries actually happened."""
    x, y, cfg = fault_case
    cfg = dataclasses.replace(cfg, sample_block=170)
    inj = FaultInjector(0.3, seed=7, max_consecutive=2)
    m = train_prf(
        x, y, cfg, seed=0,
        feeder_opts=dict(fault_hook=inj, max_retries=3, backoff=1e-4),
    )
    assert inj.injected > 0
    _assert_models_equal(m, baseline, "faulted feed")


def test_feeder_exhausted_retries_raise_feed_error_and_join_thread():
    blocks = [np.zeros((32, 4), np.uint8) for _ in range(3)]

    def always_fail(site):
        raise SimulatedFailure(f"permanent @ {site}")

    feeder = BlockFeeder(
        blocks, prefetch=2, fault_hook=always_fail, max_retries=2,
        backoff=1e-4,
    )
    with pytest.raises(FeedError, match="failed permanently after 2 retries"):
        list(feeder.sweep())
    feeder.close()
    assert not any(
        t.name == "prf-block-feeder" and t.is_alive()
        for t in threading.enumerate()
    ), "feeder thread leaked after FeedError"


def test_feeder_sweep_close_and_context_manager_join_thread():
    blocks = [np.zeros((32, 4), np.uint8) for _ in range(6)]
    feeder = BlockFeeder(blocks, prefetch=2)
    sweep = feeder.sweep()
    next(sweep)
    sweep.close()                       # abandon mid-sweep
    with BlockFeeder(blocks, prefetch=2) as f2:
        assert sum(1 for _ in f2.sweep()) == len(blocks)
    assert not any(
        t.name == "prf-block-feeder" and t.is_alive()
        for t in threading.enumerate()
    ), "feeder thread leaked after close"


def test_sweep_close_escalates_stuck_thread_to_feed_error():
    """A producer thread that outlives ``join(join_timeout)`` is a
    wedged device transfer — ``close()`` must escalate to ``FeedError``
    naming the stuck feed site, never silently leak a live thread."""
    blocks = [np.zeros((8, 2), np.uint8) for _ in range(3)]
    feeder = BlockFeeder(blocks, prefetch=1, join_timeout=0.05)
    sweep = feeder.sweep()
    next(sweep)
    # Swap in a producer that ignores cancellation (a hung device_put).
    stuck = threading.Thread(
        target=lambda: time.sleep(0.5), daemon=True, name="prf-block-feeder"
    )
    stuck.start()
    sweep._thread = stuck
    feeder._last_site = "block[1]"
    with pytest.raises(FeedError, match=r"wedged at site 'block\[1\]'"):
        sweep.close()
    # The sweep deregistered itself before raising: feeder.close() is
    # still safe, and once the transfer unwedges the thread is gone.
    feeder.close()
    stuck.join()
    assert not stuck.is_alive()


def test_feeder_retry_knobs_validated():
    blocks = [np.zeros((8, 2), np.uint8)]
    with pytest.raises(ValueError):
        BlockFeeder(blocks, max_retries=-1)
    with pytest.raises(ValueError):
        FaultInjector(1.5)
    with pytest.raises(ValueError):
        FaultInjector(0.5, max_consecutive=0)


def test_injected_feed_failures_never_change_model_property(
    fault_case, baseline
):
    """Property: for ANY fault rate/seed, growth through a
    faulty-but-retried feed is bit-identical to the clean run.

    Runs under hypothesis when it is installed; otherwise (the CI chaos
    job is gated skip-free) a deterministic seeded sweep over the same
    (rate, seed) space checks the property directly."""
    x, y, cfg = fault_case
    cfg = dataclasses.replace(cfg, sample_block=200)

    def prop(rate, seed):
        inj = FaultInjector(rate, seed=seed, max_consecutive=2)
        m = train_prf(
            x, y, cfg, seed=0,
            feeder_opts=dict(fault_hook=inj, max_retries=3, backoff=1e-4),
        )
        _assert_models_equal(m, baseline, f"rate={rate} seed={seed}")

    try:
        from hypothesis import given, settings, strategies as st
    except ImportError:
        for rate, seed in [(0.05, 1), (0.2, 17), (0.4, 4242), (0.6, 65535)]:
            prop(rate, seed)
        return

    settings(max_examples=5, deadline=None)(
        given(rate=st.floats(0.05, 0.6), seed=st.integers(0, 2 ** 16))(prop)
    )()
