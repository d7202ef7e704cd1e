"""Sibling-subtraction histogram reuse (``ForestConfig.hist_reuse``).

The acceptance bar for the reuse plane: classification forests grown
with ``hist_reuse="on"`` are BIT-IDENTICAL to ``"off"`` across
{local, mesh} x {resident, streamed} x {early-exit, fixed-depth} —
histogram counts are integer-valued f32, so ``parent - small_sibling``
is exact — including checkpoint kill/resume on both data planes. The
regression channels ([1, y, y^2]) only agree to float rounding, so
regression reuse is tolerance-gated and opt-in (``auto`` resolves to
off). A jaxpr walk proves the perf claim structurally: the reuse path
never scatters into the full ``S``-slot segment space, only the
``R = S/2`` small-child ranks. Mesh cases run in a subprocess so the
multi-device XLA flag never leaks.
"""
import dataclasses
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ForestConfig, grow_forest_streamed
from repro.core.binning import bin_dataset
from repro.core.dsi import bootstrap_counts
from repro.core.engine import (
    LocalPlane, init_hist_cache, level_task_group, resolve_hist_reuse,
    reuse_level_task_group,
)
from repro.core.forest import grow_forest, grow_forest_checkpointed
from repro.core.histograms import class_channels, level_histograms
from repro.data.tabular import make_classification, make_regression

FOREST_ARRAYS = ("feature", "threshold", "left_child", "class_counts", "value")


def _assert_forests_equal(a, b, msg=""):
    for n in FOREST_ARRAYS:
        np.testing.assert_array_equal(
            np.asarray(getattr(a, n)), np.asarray(getattr(b, n)),
            err_msg=f"{n} {msg}",
        )


@pytest.fixture(scope="module")
def reuse_case():
    x, y = make_classification(n_samples=600, n_features=13, n_classes=3, seed=3)
    cfg = ForestConfig(
        n_trees=6, max_depth=4, n_bins=16, n_classes=3, feature_mode="all"
    )
    xb, _ = bin_dataset(x, cfg.n_bins)
    w = np.asarray(
        bootstrap_counts(jax.random.PRNGKey(0), cfg.n_trees, xb.shape[0])
    ).astype(np.float32)
    return xb, y, w, cfg


def _grow(xb, y, w, cfg):
    return grow_forest(jnp.asarray(xb), jnp.asarray(y), jnp.asarray(w), cfg)


# ---------------------------------------------------------------------------
# Knob resolution & budget fallback
# ---------------------------------------------------------------------------


def test_knob_resolution_auto_is_classification_only():
    cls = ForestConfig(n_trees=2, max_depth=3, n_bins=8, n_classes=3)
    reg = dataclasses.replace(cls, regression=True, n_classes=0)
    assert cls.resolved_hist_reuse() == "on"
    assert reg.resolved_hist_reuse() == "off"
    assert dataclasses.replace(reg, hist_reuse="on").resolved_hist_reuse() == "on"
    assert dataclasses.replace(cls, hist_reuse="off").resolved_hist_reuse() == "off"
    with pytest.raises(ValueError, match="hist_reuse"):
        dataclasses.replace(cls, hist_reuse="maybe")


def test_budget_gate_falls_back_to_off(reuse_case):
    xb, y, w, cfg = reuse_case
    F = xb.shape[1]
    assert resolve_hist_reuse(cfg, F)
    tiny = dataclasses.replace(cfg, hist_reuse_budget_mb=0)
    assert not resolve_hist_reuse(tiny, F)
    # The fallback must be a silent-but-correct off run, not an error.
    _assert_forests_equal(
        _grow(xb, y, w, tiny),
        _grow(xb, y, w, dataclasses.replace(cfg, hist_reuse="off")),
        "budget fallback",
    )


# ---------------------------------------------------------------------------
# Local plane parity: resident (unfused + fused) and streamed
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("early", [True, False])
@pytest.mark.parametrize("backend", ["segment_sum", "pallas"])
def test_resident_reuse_bitwise(reuse_case, early, backend):
    xb, y, w, cfg = reuse_case
    base = dataclasses.replace(cfg, early_exit=early, hist_backend=backend)
    f_on = _grow(xb, y, w, dataclasses.replace(base, hist_reuse="on"))
    f_off = _grow(xb, y, w, dataclasses.replace(base, hist_reuse="off"))
    _assert_forests_equal(f_on, f_off, f"resident early={early} {backend}")


def test_streamed_reuse_bitwise(reuse_case):
    xb, y, w, cfg = reuse_case
    blocks = np.array_split(xb, 5)
    f_on = grow_forest_streamed(
        blocks, y, w, dataclasses.replace(cfg, hist_reuse="on")
    )
    f_off = grow_forest_streamed(
        blocks, y, w, dataclasses.replace(cfg, hist_reuse="off")
    )
    _assert_forests_equal(f_on, f_off, "streamed on-vs-off")
    _assert_forests_equal(f_on, _grow(xb, y, w, cfg), "streamed-vs-resident")


def test_checkpoint_resume_bitwise_with_reuse(reuse_case, tmp_path):
    """Kill at a level boundary, resume: the cache is a GrowthState leaf
    so the resumed run re-subtracts from the restored histograms and
    finishes bit-identical — on both local data planes."""
    from repro.checkpoint.checkpoint import CheckpointManager

    xb, y, w, cfg = reuse_case
    cfg_on = dataclasses.replace(cfg, hist_reuse="on")
    ref = _grow(xb, y, w, cfg_on)

    class Kill(Exception):
        pass

    def boom(level, _):
        if level == 2:
            raise Kill

    d = str(tmp_path / "resident")
    with pytest.raises(Kill):
        grow_forest_checkpointed(
            jnp.asarray(xb), jnp.asarray(y), jnp.asarray(w), cfg_on,
            manager=CheckpointManager(d, keep=3, save_interval=1),
            on_level=boom,
        )
    f = grow_forest_checkpointed(
        jnp.asarray(xb), jnp.asarray(y), jnp.asarray(w), cfg_on, resume_from=d
    )
    _assert_forests_equal(f, ref, "resident resume")

    cfg_st = dataclasses.replace(cfg_on, sample_block=150)
    d = str(tmp_path / "streamed")
    with pytest.raises(Kill):
        grow_forest_streamed(
            xb, y, w, cfg_st,
            manager=CheckpointManager(d, keep=3, save_interval=1),
            on_level=boom,
        )
    f = grow_forest_streamed(xb, y, w, cfg_st, resume_from=d)
    _assert_forests_equal(f, ref, "streamed resume")


# ---------------------------------------------------------------------------
# Regression: tolerance-gated, opt-in
# ---------------------------------------------------------------------------


def test_regression_reuse_within_tolerance():
    """[1, y, y^2] channels are not integer-valued, so parent - small
    only matches the direct sum to float rounding. Opt-in "on" must
    give the same tree STRUCTURE on a fixture without razor-thin gain
    ties, and leaf values within float tolerance."""
    x, y = make_regression(n_samples=500, n_features=10, seed=5)
    cfg = ForestConfig(
        n_trees=4, max_depth=4, n_bins=16, regression=True, n_classes=0,
        feature_mode="all",
    )
    xb, _ = bin_dataset(x, cfg.n_bins)
    w = np.asarray(
        bootstrap_counts(jax.random.PRNGKey(2), cfg.n_trees, xb.shape[0])
    ).astype(np.float32)
    f_on = _grow(xb, y, w, dataclasses.replace(cfg, hist_reuse="on"))
    f_off = _grow(xb, y, w, dataclasses.replace(cfg, hist_reuse="off"))
    for n in ("feature", "threshold", "left_child"):
        np.testing.assert_array_equal(
            np.asarray(getattr(f_on, n)), np.asarray(getattr(f_off, n)),
            err_msg=f"regression structure {n}",
        )
    np.testing.assert_allclose(
        np.asarray(f_on.value), np.asarray(f_off.value), rtol=1e-5, atol=1e-5
    )


# ---------------------------------------------------------------------------
# Root-histogram audit: dimred's root sweep == growth's level-0 row
# ---------------------------------------------------------------------------


def test_root_hist_bitwise_across_slot_geometries(reuse_case):
    """Shared values, separate passes: the dimred root-gain histogram
    (n_slots=1) and growth's level-0 histogram row 0 (n_slots=S
    off-path, n_slots=R packed reuse path) are the same segment_sum
    over the same sample order — bitwise equal, all three geometries."""
    xb, y, w, cfg = reuse_case
    cfg = cfg.resolved(xb.shape[1])
    base = class_channels(jnp.asarray(y), cfg.n_classes)
    slot0 = jnp.zeros_like(jnp.asarray(w), dtype=jnp.int32)
    rows = {
        n_slots: np.asarray(level_histograms(
            jnp.asarray(xb), base, jnp.asarray(w), slot0,
            n_slots=n_slots, n_bins=cfg.n_bins, backend="segment_sum",
        )[:, 0])
        for n_slots in (1, cfg.max_splits_per_level, cfg.frontier)
    }
    ref = rows.pop(1)
    for n_slots, row in rows.items():
        np.testing.assert_array_equal(ref, row, err_msg=f"n_slots={n_slots}")


# ---------------------------------------------------------------------------
# Structural perf proof: large children are never re-scattered
# ---------------------------------------------------------------------------


def _scatter_dims(jaxpr):
    """All leading output dims in a jaxpr tree — segment_sum lowers to
    scatter/jit-call shapes whose first dim is the segment count."""
    import jax.extend.core as jex

    dims = set()

    def walk(j):
        for eqn in j.eqns:
            for v in eqn.outvars:
                shp = getattr(getattr(v, "aval", None), "shape", ())
                if shp:
                    dims.add(int(shp[0]))
            for val in eqn.params.values():
                for sub in jax.tree_util.tree_leaves(
                    val, is_leaf=lambda x: isinstance(
                        x, (jex.Jaxpr, jex.ClosedJaxpr))
                ):
                    if isinstance(sub, jex.ClosedJaxpr):
                        walk(sub.jaxpr)
                    elif isinstance(sub, jex.Jaxpr):
                        walk(sub)

    walk(jaxpr)
    return dims


def test_reuse_never_scatters_full_slot_segments(reuse_case):
    """Off-path T_GR scatters into S*B + B segments (S slots + dump);
    the reuse task group must only ever scatter into R*B + B (small
    children + dump) — the large-child half is reconstructed by
    subtraction, never re-scattered. S=32 vs R=16 here, so the segment
    counts (528 vs 272) cannot collide with any other dimension."""
    xb, y, w, _ = reuse_case
    cfg = ForestConfig(
        n_trees=6, max_depth=5, n_bins=16, n_classes=3, feature_mode="all",
        hist_backend="segment_sum",
    ).resolved(xb.shape[1])
    S, R, B = cfg.frontier, cfg.max_splits_per_level, cfg.n_bins
    assert (S, R) == (32, 16)
    full_seg, packed_seg = S * B + B, R * B + B
    xb_d, base = jnp.asarray(xb), class_channels(jnp.asarray(y), cfg.n_classes)
    w_d = jnp.asarray(w)
    slot = jnp.zeros_like(w_d, dtype=jnp.int32)
    slot_node = jnp.full((cfg.n_trees, S), -1, jnp.int32).at[:, 0].set(0)
    plane = LocalPlane(None)

    off = jax.make_jaxpr(
        lambda *a: level_task_group(*a, cfg, plane)
    )(xb_d, base, w_d, slot, slot_node)
    cache = init_hist_cache(cfg, xb.shape[1])
    on = jax.make_jaxpr(
        lambda *a: reuse_level_task_group(*a, cfg, plane)
    )(xb_d, base, w_d, slot, slot_node, cache)

    off_dims, on_dims = _scatter_dims(off.jaxpr), _scatter_dims(on.jaxpr)
    assert full_seg in off_dims, "off path should scatter all S slots"
    assert full_seg not in on_dims, "reuse path re-scattered large children"
    assert packed_seg in on_dims, "reuse path should scatter R ranks"


# ---------------------------------------------------------------------------
# Mesh plane (subprocess: 8 host devices)
# ---------------------------------------------------------------------------


def test_mesh_reuse_parity_and_resume():
    """Mesh resident (psum + psum_scatter) and mesh streamed forests
    with reuse on == the local off-mode forest bitwise; a mesh-streamed
    run killed at a level boundary resumes bit-identically (the cache
    rides the checkpoint, feature-sharded)."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import dataclasses, tempfile
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.core import ForestConfig
        from repro.core.binning import bin_dataset
        from repro.core.distributed import (
            _grow_sharded, grow_forest_streamed_sharded,
        )
        from repro.core.dsi import bootstrap_counts
        from repro.core.forest import grow_forest
        from repro.core.histograms import class_channels
        from repro.checkpoint.checkpoint import CheckpointManager
        from repro.data.tabular import make_classification
        from repro.launch.mesh import make_mesh

        x, y = make_classification(n_samples=640, n_features=16, n_classes=3,
                                   seed=2)
        cfg0 = ForestConfig(n_trees=6, max_depth=4, n_bins=16, n_classes=3,
                            feature_mode="all", hist_reuse="on")
        xb, _ = bin_dataset(x, cfg0.n_bins)
        y_np = np.asarray(y)
        xb_dev, y_dev = jnp.asarray(xb), jnp.asarray(y)
        w = bootstrap_counts(jax.random.PRNGKey(1), cfg0.n_trees,
                             xb.shape[0]).astype(jnp.float32)
        w_np = np.asarray(w)
        mesh = make_mesh((4, 2), ("data", "model"))
        ARRS = ("feature", "threshold", "left_child", "class_counts", "value")
        f_ref = grow_forest(xb_dev, y_dev, w,
                            dataclasses.replace(cfg0, hist_reuse="off"))

        def check(f, tag):
            for n in ARRS:
                np.testing.assert_array_equal(
                    np.asarray(getattr(f, n)), np.asarray(getattr(f_ref, n)),
                    err_msg=f"{n} {tag}")

        for hist_reduce in ("psum", "psum_scatter"):
            cfg = dataclasses.replace(cfg0, hist_reduce=hist_reduce)
            def kernel(xb_loc, y_loc, w_loc, _cfg=cfg):
                base_loc = class_channels(y_loc, _cfg.n_classes)
                return _grow_sharded(xb_loc, base_loc, w_loc, None, _cfg,
                                     sample_axes=("data",),
                                     feature_axis="model")
            f_mesh = jax.jit(jax.shard_map(
                kernel, mesh=mesh,
                in_specs=(P("data", "model"), P("data"), P(None, "data")),
                out_specs=P(),
                check_vma=False,
            ))(xb_dev, y_dev, w)
            check(f_mesh, f"resident {hist_reduce}")
            cfg_st = dataclasses.replace(cfg, sample_block=170)
            check(grow_forest_streamed_sharded(xb, y_np, w_np, cfg_st, mesh),
                  f"streamed {hist_reduce}")
        print("MESH_REUSE_PARITY_OK")

        cfg_st = dataclasses.replace(cfg0, sample_block=170)

        class Kill(Exception):
            pass

        def boom(level, _):
            if level == 2:
                raise Kill

        d = tempfile.mkdtemp()
        try:
            grow_forest_streamed_sharded(
                xb, y_np, w_np, cfg_st, mesh,
                manager=CheckpointManager(d, keep=3, save_interval=1),
                on_level=boom)
            raise AssertionError("kill did not fire")
        except Kill:
            pass
        check(grow_forest_streamed_sharded(xb, y_np, w_np, cfg_st, mesh,
                                           resume_from=d),
              "streamed resume")
        print("MESH_REUSE_RESUME_OK")
    """)
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=900,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "MESH_REUSE_RESUME_OK" in out.stdout
