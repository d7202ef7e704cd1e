"""Defense-in-depth drills (ISSUE 7 tentpole): checksummed checkpoints
and poisoned-block quarantine.

* **Checkpoint integrity** — every leaf carries a CRC32 in the
  manifest; a seeded byte-flipper (``CheckpointCorruptor``) must be
  caught before deserialization, ``restore_latest_valid`` must walk
  back past corrupt AND torn steps to the newest verifiable one, and a
  fully-corrupt directory must degrade to a fresh start — never a
  poisoned model.
* **Corrupted-resume parity** — killing growth, corrupting the newest
  checkpoint, and resuming must produce the bit-identical model on
  {local, mesh} x {resident, streamed} (mesh in a subprocess so the
  8-device XLA flag never leaks).
* **Poisoned blocks** — NaN/Inf cells and out-of-range labels under
  ``bad_block_policy``: ``"raise"`` names the block and columns,
  ``"sanitize"`` / ``"quarantine"`` are deterministic run-to-run, and
  on clean data validation is a bitwise no-op.
"""
import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import (
    CheckpointCorruptionError, CheckpointManager, latest_step, list_steps,
    restore_checkpoint, restore_latest_valid, save_checkpoint,
    verify_checkpoint,
)
from repro.core import ForestConfig, train_prf
from repro.data.pipeline import (
    BlockFeeder, BlockValidator, DataIntegrityError, screen_blocks,
)
from repro.data.tabular import make_classification
from repro.launch.fault import CheckpointCorruptor, SimulatedFailure

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


def _tree(seed):
    rng = np.random.default_rng(seed)
    return {
        "w": jnp.asarray(rng.normal(size=(7, 5)).astype(np.float32)),
        "slots": [jnp.asarray(rng.integers(0, 99, size=(11,), dtype=np.int32))],
        "step": jnp.asarray(seed, np.int32),
    }


def _trees_equal(a, b):
    fa = jax.tree_util.tree_leaves(a)
    fb = jax.tree_util.tree_leaves(b)
    assert len(fa) == len(fb)
    for la, lb in zip(fa, fb):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


# ---------------------------------------------------------------------------
# Checksummed checkpoints: CRC manifest, byte flips, walk-back
# ---------------------------------------------------------------------------


def test_manifest_carries_crc_and_roundtrips(tmp_path):
    import msgpack

    d = str(tmp_path)
    path = save_checkpoint(_tree(1), d, 1)
    with open(os.path.join(path, "manifest.msgpack"), "rb") as f:
        manifest = msgpack.unpackb(f.read())
    assert all(isinstance(e["crc32"], int) for e in manifest["leaves"])
    verify_checkpoint(d, 1)                    # every leaf passes its CRC
    restored, step = restore_checkpoint(_tree(0), d, 1)
    assert step == 1
    _trees_equal(restored, _tree(1))


def test_byte_flip_caught_before_deserialization(tmp_path):
    d = str(tmp_path)
    save_checkpoint(_tree(1), d, 1)
    assert CheckpointCorruptor(seed=0).corrupt(d) == 1
    with pytest.raises(CheckpointCorruptionError):
        verify_checkpoint(d, 1)
    with pytest.raises(CheckpointCorruptionError):
        restore_checkpoint(_tree(0), d, 1)
    # verify=False is the escape hatch that shows WHY verification is
    # load-bearing: without it the flip may deserialize silently.
    assert restore_latest_valid(_tree(0), d) is None


def test_corruptor_is_deterministic():
    import tempfile

    def run():
        d = tempfile.mkdtemp()
        save_checkpoint(_tree(3), d, 1)
        CheckpointCorruptor(seed=7, n_bytes=8).corrupt(d)
        path = os.path.join(d, "step_00000001")
        return {
            f: open(os.path.join(path, f), "rb").read()
            for f in sorted(os.listdir(path)) if f.endswith(".npy")
        }

    assert run() == run()                      # same bytes flipped both runs


def test_restore_latest_valid_walks_back_past_corruption(tmp_path):
    d = str(tmp_path)
    save_checkpoint(_tree(1), d, 1)
    save_checkpoint(_tree(2), d, 2)
    CheckpointCorruptor(seed=0).corrupt(d)     # newest = step 2
    skipped = []
    with pytest.warns(RuntimeWarning, match="skipping corrupt checkpoint"):
        restored, step = restore_latest_valid(
            _tree(0), d, on_skip=lambda s, e: skipped.append(s)
        )
    assert step == 1 and skipped == [2]
    _trees_equal(restored, _tree(1))           # exact step-1 values


def test_fully_corrupt_directory_degrades_to_fresh_start(tmp_path):
    d = str(tmp_path)
    for s in (1, 2):
        save_checkpoint(_tree(s), d, s)
        CheckpointCorruptor(seed=s).corrupt(d, s)
    with pytest.warns(RuntimeWarning):
        assert restore_latest_valid(_tree(0), d) is None
    mgr = CheckpointManager(d)
    with pytest.warns(RuntimeWarning), pytest.raises(FileNotFoundError):
        mgr.restore_latest_valid(_tree(0))


def test_manifest_without_crc_still_restores(tmp_path):
    """Backward compat: pre-integrity manifests (no crc32 key) skip the
    CRC check but keep shape/dtype verification."""
    import msgpack

    d = str(tmp_path)
    path = save_checkpoint(_tree(4), d, 1)
    mpath = os.path.join(path, "manifest.msgpack")
    with open(mpath, "rb") as f:
        manifest = msgpack.unpackb(f.read())
    for e in manifest["leaves"]:
        del e["crc32"]
    with open(mpath, "wb") as f:
        f.write(msgpack.packb(manifest))
    restored, step = restore_checkpoint(_tree(0), d, 1)
    _trees_equal(restored, _tree(4))


def test_latest_step_ignores_stray_and_malformed_entries(tmp_path):
    """Step discovery over a dirty directory: stray files, a file
    masquerading as a step dir, orphaned tmp dirs — none may crash or
    miscount ``latest_step``."""
    d = str(tmp_path)
    save_checkpoint(_tree(1), d, 1)
    save_checkpoint(_tree(2), d, 7)
    (tmp_path / "step_garbage").write_text("not a step")
    (tmp_path / "step_00000099").write_text("a FILE, not a step dir")
    (tmp_path / "README").write_text("stray")
    (tmp_path / ".tmp_save_dead").mkdir()
    assert list_steps(d) == [1, 7]
    assert latest_step(d) == 7
    assert latest_step(str(tmp_path / "missing")) is None
    # manager init garbage-collects the orphaned tmp dir
    CheckpointManager(d)
    assert not (tmp_path / ".tmp_save_dead").exists()
    assert (tmp_path / "step_garbage").exists()     # strangers untouched


def test_torn_write_never_clobbers_previous_step(tmp_path):
    """Kill a save in the torn-write window (after the complete tmp
    write, before the atomic rename): the previous step must stay the
    restorable latest, and the orphan tmp dir must be GC'd on the next
    manager init."""
    d = str(tmp_path)
    save_checkpoint(_tree(1), d, 1)

    def tear(site):
        if site == "pre_rename":
            raise SimulatedFailure("killed before rename")

    with pytest.raises(SimulatedFailure):
        save_checkpoint(_tree(2), d, 2, fault_hook=tear)
    assert latest_step(d) == 1                 # step 2 never materialized
    assert any(f.startswith(".tmp_save_") for f in os.listdir(d))
    restored, step = restore_latest_valid(_tree(0), d)
    assert step == 1
    _trees_equal(restored, _tree(1))
    CheckpointManager(d)                       # crash-retry supervisor
    assert not any(f.startswith(".tmp_save_") for f in os.listdir(d))

    # Tear mid-leaf too: nothing durable may change either.
    def tear_leaf(site):
        if site == "leaf[1]":
            raise SimulatedFailure("killed mid-leaf")

    mgr = CheckpointManager(d, save_interval=1, fault_hook=tear_leaf)
    with pytest.raises(SimulatedFailure):
        mgr.maybe_save(_tree(3), 3)
    assert latest_step(d) == 1


# ---------------------------------------------------------------------------
# Corrupted-resume parity drills
# ---------------------------------------------------------------------------


class _Kill(Exception):
    pass


@pytest.fixture(scope="module")
def drill_case():
    x, y = make_classification(n_samples=600, n_features=13, n_classes=3, seed=3)
    cfg = ForestConfig(
        n_trees=6, max_depth=4, n_bins=16, n_classes=3, feature_mode="all"
    )
    return x, y, cfg


@pytest.fixture(scope="module")
def drill_baseline(drill_case):
    x, y, cfg = drill_case
    return train_prf(x, y, cfg, seed=0)


@pytest.mark.parametrize("streamed", [False, True], ids=["resident", "streamed"])
def test_corrupted_resume_bit_identical_local(
    tmp_path, drill_case, drill_baseline, streamed
):
    """The corruption drill: kill growth at a level boundary, flip bytes
    in the NEWEST checkpoint, resume. The walk-back restores the
    previous step, regrows one extra level, and the final model is
    bit-identical to an uninterrupted run."""
    x, y, cfg = drill_case
    if streamed:
        cfg = dataclasses.replace(cfg, sample_block=170)
    kill_at = 2
    d = str(tmp_path / ("st" if streamed else "rs"))

    def boom(level, _):
        if level == kill_at:
            raise _Kill

    with pytest.raises(_Kill):
        train_prf(x, y, cfg, seed=0, checkpoint_dir=d, on_level=boom)
    assert CheckpointCorruptor(seed=0).corrupt(d) == kill_at

    resumed = []
    with pytest.warns(RuntimeWarning, match="skipping corrupt checkpoint"):
        m = train_prf(
            x, y, cfg, seed=0, resume_from=d,
            on_level=lambda level, _: resumed.append(level),
        )
    # Walk-back landed on step kill_at-1, so the crash level regrows.
    assert min(resumed) == kill_at, resumed
    _assert_models_equal(m, drill_baseline, f"corrupt-resume streamed={streamed}")
    np.testing.assert_array_equal(m.predict(x), drill_baseline.predict(x))


def test_all_corrupt_resume_is_fresh_start(tmp_path, drill_case, drill_baseline):
    """Every checkpoint corrupt -> resume degrades to a from-scratch
    retrain (ElasticRunner convention), still bit-identical."""
    x, y, cfg = drill_case
    kill_at = 2
    d = str(tmp_path / "allbad")

    def boom(level, _):
        if level == kill_at:
            raise _Kill

    with pytest.raises(_Kill):
        train_prf(x, y, cfg, seed=0, checkpoint_dir=d, on_level=boom)
    for s in list_steps(d):
        CheckpointCorruptor(seed=s).corrupt(d, s)
    with pytest.warns(RuntimeWarning):
        m = train_prf(x, y, cfg, seed=0, resume_from=d)
    _assert_models_equal(m, drill_baseline, "all-corrupt fresh start")


def test_corrupted_resume_bit_identical_mesh():
    code = textwrap.dedent("""
        import os, warnings
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
        from repro.launch.fault import CheckpointCorruptor
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
            kill_at = 2
            d = tempfile.mkdtemp()

            def boom(level, _):
                if level == kill_at:
                    raise Kill

            try:
                grow(manager=CheckpointManager(d, keep=5, save_interval=1),
                     resume_from=None, on_level=boom)
                raise AssertionError("kill did not fire")
            except Kill:
                pass
            assert CheckpointCorruptor(seed=0).corrupt(d) == kill_at
            resumed = []
            with warnings.catch_warnings(record=True) as wrec:
                warnings.simplefilter("always")
                f = grow(manager=None, resume_from=d,
                         on_level=lambda level, _: resumed.append(level))
            assert any("skipping corrupt checkpoint" in str(x.message)
                       for x in wrec), (tag, "walk-back never fired")
            assert min(resumed) == kill_at, (tag, resumed)
            for n in ARRS:
                np.testing.assert_array_equal(
                    np.asarray(getattr(f, n)),
                    np.asarray(getattr(local, n)),
                    err_msg=f"{n} {tag}")

        drill(lambda **kw: grow_sharded_checkpointed(
            xb, y_np, w, cfg, mesh, **kw), "mesh-resident")
        cfgs = ForestConfig(n_trees=6, max_depth=4, n_bins=16, n_classes=3,
                            feature_mode="all", sample_block=170).resolved(16)
        drill(lambda **kw: grow_forest_streamed(
            xb, y_np, w, cfgs, mesh=mesh, **kw), "mesh-streamed")
        print("MESH_CORRUPT_RESUME_OK")
    """)
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=900,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "MESH_CORRUPT_RESUME_OK" in out.stdout


# ---------------------------------------------------------------------------
# Poisoned-block drills: raise / sanitize / quarantine
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def poison_case(drill_case):
    """Rows 310-320 of column 3 go NaN, row 330 of column 7 goes Inf —
    all inside block 2 of the sample_block=150 sweep."""
    x, y, cfg = drill_case
    xp = np.array(x, dtype=np.float64)
    xp[310:320, 3] = np.nan
    xp[330, 7] = np.inf
    return xp, np.asarray(y), dataclasses.replace(cfg, sample_block=150)


def test_clean_data_validation_is_bitwise_noop(drill_case):
    x, y, cfg = drill_case
    base_r = train_prf(x, y, cfg, seed=0, bad_block_policy=None)
    assert base_r.quarantine is None
    for policy in ("raise", "sanitize", "quarantine"):
        m = train_prf(x, y, cfg, seed=0, bad_block_policy=policy)
        assert m.quarantine is not None and m.quarantine.clean
        _assert_models_equal(m, base_r, f"clean resident {policy}")
    cfgs = dataclasses.replace(cfg, sample_block=170)
    base_s = train_prf(x, y, cfgs, seed=0, bad_block_policy=None)
    for policy in ("raise", "sanitize", "quarantine"):
        m = train_prf(x, y, cfgs, seed=0, bad_block_policy=policy)
        assert m.quarantine.counters()["blocks_quarantined"] == 0
        _assert_models_equal(m, base_s, f"clean streamed {policy}")


def test_raise_policy_names_block_and_columns(poison_case):
    x, y, cfg = poison_case
    with pytest.raises(DataIntegrityError) as ei:
        train_prf(x, y, cfg, seed=0, bad_block_policy="raise")
    err = ei.value
    assert err.block_index == 2                # rows 300-449
    assert err.columns == (3, 7)
    assert err.reason == "nonfinite"
    assert "block 2" in str(err) and "[3, 7]" in str(err)


def test_raise_is_the_default_policy(poison_case):
    x, y, cfg = poison_case
    with pytest.raises(DataIntegrityError):
        train_prf(x, y, cfg, seed=0)


def test_sanitize_policy_is_deterministic(poison_case):
    x, y, cfg = poison_case
    a = train_prf(x, y, cfg, seed=0, bad_block_policy="sanitize")
    b = train_prf(x, y, cfg, seed=0, bad_block_policy="sanitize")
    _assert_models_equal(a, b, "sanitize run-to-run")
    assert a.quarantine.sanitized_cells == 11  # 10 NaN + 1 Inf
    assert a.quarantine.quarantined == []
    assert not a.quarantine.clean


def test_quarantine_policy_drops_block_deterministically(poison_case):
    x, y, cfg = poison_case
    a = train_prf(x, y, cfg, seed=0, bad_block_policy="quarantine")
    b = train_prf(x, y, cfg, seed=0, bad_block_policy="quarantine")
    _assert_models_equal(a, b, "quarantine run-to-run")
    assert a.quarantine.quarantined == [2]
    assert a.quarantine.counters()["blocks_quarantined"] == 1
    # the report survives a predict-backend swap
    assert a.with_predict_backend("xla").quarantine is a.quarantine


def test_poisoned_labels_sanitized_and_counted(drill_case):
    x, y, cfg = drill_case
    yb = np.array(y)
    yb[5:10] = 7                               # out of range for 3 classes
    cfgs = dataclasses.replace(cfg, sample_block=170)
    with pytest.raises(DataIntegrityError) as ei:
        train_prf(x, yb, cfgs, seed=0, bad_block_policy="raise")
    assert ei.value.reason == "label" and ei.value.block_index == 0
    a = train_prf(x, yb, cfgs, seed=0, bad_block_policy="sanitize")
    b = train_prf(x, yb, cfgs, seed=0, bad_block_policy="sanitize")
    _assert_models_equal(a, b, "label sanitize run-to-run")
    assert a.quarantine.sanitized_labels == 5


def test_resident_path_policies(poison_case):
    """The resident dataset is ONE block: raise still names columns,
    sanitize still trains deterministically, quarantine is a typed
    refusal pointing at streaming."""
    x, y, cfg = poison_case
    resident = dataclasses.replace(cfg, sample_block=0)
    with pytest.raises(DataIntegrityError) as ei:
        train_prf(x, y, resident, seed=0, bad_block_policy="raise")
    assert ei.value.columns == (3, 7)
    a = train_prf(x, y, resident, seed=0, bad_block_policy="sanitize")
    b = train_prf(x, y, resident, seed=0, bad_block_policy="sanitize")
    _assert_models_equal(a, b, "resident sanitize run-to-run")
    with pytest.raises(DataIntegrityError, match="sample_block"):
        train_prf(x, y, resident, seed=0, bad_block_policy="quarantine")


def test_validator_unit_findings():
    v = BlockValidator("quarantine", n_features=4, n_classes=3)
    clean = np.zeros((8, 4), np.float32)
    assert v.check(clean, 0, np.zeros(8, np.int32)) is None
    bad = clean.copy()
    bad[2, 1] = np.nan
    issue = v.check(bad, 5)
    assert issue.reason == "nonfinite" and issue.columns == (1,)
    assert "block 5" in issue.describe()
    assert v.check(np.zeros((8, 9), np.float32), 1).reason == "shape"
    issue = v.check(clean, 2, np.array([0, 1, 2, 3, -1, 0, 0, 0]))
    assert issue.reason == "label" and issue.bad_labels == 2
    with pytest.raises(ValueError, match="bad_block_policy"):
        BlockValidator("retry")


def test_screen_raise_does_not_mutate_inputs():
    blocks = [np.zeros((4, 3), np.float32), np.full((4, 3), np.nan)]
    y = np.zeros(8, np.int32)
    with pytest.raises(DataIntegrityError):
        screen_blocks(blocks, y, policy="raise", n_classes=3)
    assert np.isnan(blocks[1]).all()           # untouched on raise


def test_feeder_quarantines_shape_drift_and_skips_blocks():
    blocks = [
        np.zeros((16, 4), np.float32),
        np.zeros((16, 9), np.float32),         # drifted width
        np.full((16, 4), np.inf),              # poisoned
        np.zeros((16, 4), np.float32),
    ]
    feeder = BlockFeeder(
        blocks, prefetch=2, validator=BlockValidator("quarantine")
    )
    assert feeder.quarantined == (1, 2)
    assert feeder.live_blocks == (0, 3)
    with feeder:
        got = list(feeder.sweep())
    assert len(got) == 2                       # quarantined never transferred
    assert feeder.report.counters()["blocks_quarantined"] == 2


def test_feeder_refuses_fully_quarantined_feed():
    blocks = [np.full((8, 2), np.nan) for _ in range(2)]
    with pytest.raises(DataIntegrityError, match="every block quarantined"):
        BlockFeeder(blocks, validator=BlockValidator("quarantine"))
    with pytest.raises(ValueError, match="out of range"):
        BlockFeeder([np.zeros((8, 2), np.float32)], quarantined=[5])
    with pytest.raises(ValueError, match="join_timeout"):
        BlockFeeder([np.zeros((8, 2), np.float32)], join_timeout=0)
