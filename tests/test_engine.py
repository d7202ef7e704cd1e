"""Unified growth-engine parity matrix (core/engine.py).

The acceptance bar for the task-DAG engine as the one growth
implementation: {local, mesh} x {early-exit, fixed-depth} x
{streamed, resident} all produce bit-identical ``Forest`` arrays on the
small fixtures (DSI counts are integer-valued, so every histogram
accumulation order is exact f32 integer arithmetic), the ``tree_chunk``
remainder padding is exact, and ``GrowthState`` round-trips ``jax.jit``
as a pytree. The mesh cases run in a subprocess so the multi-device XLA
flag never leaks into other tests.
"""
import dataclasses
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ForestConfig, GrowthState, grow_forest_streamed
from repro.core.binning import bin_dataset
from repro.core.dimred import random_feature_mask
from repro.core.dsi import bootstrap_counts
from repro.core.engine import init_growth_state, LocalPlane
from repro.core.forest import chunked_level_scores, grow_forest
from repro.data.tabular import make_classification, make_regression

FOREST_ARRAYS = ("feature", "threshold", "left_child", "class_counts", "value")


def _assert_forests_equal(a, b, msg=""):
    for n in FOREST_ARRAYS:
        np.testing.assert_array_equal(
            np.asarray(getattr(a, n)), np.asarray(getattr(b, n)),
            err_msg=f"{n} {msg}",
        )


@pytest.fixture(scope="module")
def grow_case():
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
# Early-exit scheduling
# ---------------------------------------------------------------------------


def test_early_exit_matches_fixed_depth(grow_case):
    xb, y, w, cfg = grow_case
    f_ee = _grow(xb, y, w, dataclasses.replace(cfg, early_exit=True))
    f_fx = _grow(xb, y, w, dataclasses.replace(cfg, early_exit=False))
    _assert_forests_equal(f_ee, f_fx, "early_exit")


def test_early_exit_matches_on_depth_starved_forest(grow_case):
    """Deep budget, tiny data: every frontier dies well before max_depth,
    so the while_loop actually exits early — and still matches."""
    xb, y, w, cfg = grow_case
    deep = dataclasses.replace(cfg, max_depth=12, min_samples_split=64)
    f_ee = _grow(xb, y, w, dataclasses.replace(deep, early_exit=True))
    f_fx = _grow(xb, y, w, dataclasses.replace(deep, early_exit=False))
    _assert_forests_equal(f_ee, f_fx, "early_exit deep")


# ---------------------------------------------------------------------------
# Sample-block streaming
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("prefetch", [0, 2])
def test_streamed_blocks_match_resident(grow_case, prefetch):
    """>= 4 host-fed blocks -> the exact resident forest; no device call
    ever sees the full [N, F] matrix (the block list IS the feed API).
    prefetch=0 is the synchronous feed, prefetch=2 the async
    double-buffered BlockFeeder — both run the fused route+hist pass
    and must be bit-identical to the resident engine."""
    xb, y, w, cfg = grow_case
    blocks = np.array_split(xb, 5)
    assert len(blocks) >= 4 and max(b.shape[0] for b in blocks) < xb.shape[0]
    f_st = grow_forest_streamed(blocks, y, w, cfg, prefetch=prefetch)
    _assert_forests_equal(
        f_st, _grow(xb, y, w, cfg), f"streamed blocks prefetch={prefetch}"
    )


@pytest.mark.parametrize("hist_reuse", ["off", "on"])
def test_streamed_masked_blocks_match_resident(grow_case, hist_reuse):
    """A random per-tree feature mask through the one-device streamed
    driver (mesh=None) == the resident masked forest, bitwise, with
    sibling-histogram reuse off and on."""
    xb, y, w, cfg = grow_case
    cfg = dataclasses.replace(cfg, hist_reuse=hist_reuse)
    mask = np.asarray(random_feature_mask(
        jax.random.PRNGKey(5), n_trees=cfg.n_trees,
        n_features=xb.shape[1], n_selected=6,
    ))
    assert not mask.all() and mask.any(axis=1).all()
    f_st = grow_forest_streamed(np.array_split(xb, 5), y, w, cfg, mask)
    f_rs = grow_forest(
        jnp.asarray(xb), jnp.asarray(y), jnp.asarray(w), cfg,
        jnp.asarray(mask),
    )
    _assert_forests_equal(f_st, f_rs, f"streamed mask reuse={hist_reuse}")


def test_streamed_rejects_empty_block_sequence(grow_case):
    """An empty block list must raise a clear ValueError, not IndexError
    on blocks[0]."""
    xb, y, w, cfg = grow_case
    with pytest.raises(ValueError, match="empty block sequence"):
        grow_forest_streamed([], y, w, cfg)
    with pytest.raises(ValueError, match="empty block sequence"):
        grow_forest_streamed(
            xb[:0], y[:0], w[:, :0],
            dataclasses.replace(cfg, sample_block=64),
        )


def test_streamed_array_source_uses_sample_block(grow_case):
    """Array/memmap source: config.sample_block slices the host views."""
    xb, y, w, cfg = grow_case
    cfg_sb = dataclasses.replace(cfg, sample_block=150)   # 600 -> 4 blocks
    f_st = grow_forest_streamed(xb, y, w, cfg_sb)
    _assert_forests_equal(f_st, _grow(xb, y, w, cfg), "streamed array")


def test_streamed_rejects_mismatched_blocks(grow_case):
    xb, y, w, cfg = grow_case
    with pytest.raises(ValueError):
        grow_forest_streamed([xb[:100]], y, w, cfg)


def test_streamed_rejects_array_without_sample_block(grow_case):
    """An array source with sample_block=0 would silently feed the whole
    [N, F] matrix as one device block — exactly what the out-of-core
    path exists to avoid, so it must refuse."""
    xb, y, w, cfg = grow_case
    assert cfg.sample_block == 0
    with pytest.raises(ValueError, match="sample_block"):
        grow_forest_streamed(xb, y, w, cfg)


def test_resident_sample_block_knob_is_exact(grow_case):
    """Device-side blocked histogram accumulation (non-divisible final
    block included) == the one-pass histogram, bitwise."""
    xb, y, w, cfg = grow_case
    for nb in (150, 256):     # divides N / leaves a remainder
        f_sb = _grow(xb, y, w, dataclasses.replace(cfg, sample_block=nb))
        _assert_forests_equal(f_sb, _grow(xb, y, w, cfg), f"sample_block={nb}")


def test_streamed_regression_close():
    """Regression channels are float sums — blocked accumulation agrees
    to rounding, not bitwise; predictions must still agree closely."""
    x, y = make_regression(500, 11, seed=4)
    cfg = ForestConfig(
        n_trees=5, max_depth=4, n_bins=16, regression=True, feature_mode="all"
    )
    xb, _ = bin_dataset(x, cfg.n_bins)
    w = np.asarray(
        bootstrap_counts(jax.random.PRNGKey(2), cfg.n_trees, xb.shape[0])
    ).astype(np.float32)
    yf = y.astype(np.float32)
    f_st = grow_forest_streamed(np.array_split(xb, 4), yf, w, cfg)
    f_rs = _grow(xb, yf, w, cfg)
    np.testing.assert_array_equal(
        np.asarray(f_st.feature), np.asarray(f_rs.feature)
    )
    np.testing.assert_allclose(
        np.asarray(f_st.value), np.asarray(f_rs.value), rtol=1e-5, atol=1e-5
    )


# ---------------------------------------------------------------------------
# Streamed OOB + prediction (the sample-block carriers)
# ---------------------------------------------------------------------------


def test_streamed_oob_and_predict_match_resident(grow_case):
    """Blocked OOB accuracy and prediction == resident, bitwise (OOB
    correct/total counts are exact f32 integer sums; labels are
    per-sample)."""
    from repro.core.voting import (
        oob_accuracy, oob_accuracy_streamed, predict, predict_scores,
        predict_scores_streamed, predict_streamed,
    )

    xb, y, w, cfg = grow_case
    forest = _grow(xb, y, w, cfg)
    blocks = np.array_split(xb, 5)
    xb_dev, y_dev, w_dev = jnp.asarray(xb), jnp.asarray(y), jnp.asarray(w)

    np.testing.assert_array_equal(
        np.asarray(oob_accuracy_streamed(forest, blocks, y, w)),
        np.asarray(oob_accuracy(forest, xb_dev, y_dev, w_dev)),
    )
    # Array source + sample_block slicing, prefetch on and off.
    for prefetch in (0, 2):
        np.testing.assert_array_equal(
            np.asarray(oob_accuracy_streamed(
                forest, xb, y, w, sample_block=130, prefetch=prefetch,
            )),
            np.asarray(oob_accuracy(forest, xb_dev, y_dev, w_dev)),
        )
    np.testing.assert_array_equal(
        np.asarray(predict_streamed(forest, blocks)),
        np.asarray(predict(forest, xb_dev)),
    )
    np.testing.assert_array_equal(
        np.asarray(predict_scores_streamed(forest, xb, sample_block=200)),
        np.asarray(predict_scores(forest, xb_dev)),
    )
    with pytest.raises(ValueError, match="sample_block"):
        oob_accuracy_streamed(forest, xb, y, w)   # array source needs blocks


def test_streamed_oob_r2_bitwise():
    """Blocked OOB R^2 == resident, BITWISE: both paths compute the
    per-sample moment terms with one shared jitted kernel and reduce
    them in host float64 (the streamed side Neumaier-compensated per
    block), so the single f32 rounding at the end agrees exactly —
    across different block splits too."""
    from repro.core.voting import oob_r2, oob_r2_streamed

    x, y = make_regression(500, 11, seed=4)
    cfg = ForestConfig(
        n_trees=5, max_depth=4, n_bins=16, regression=True, feature_mode="all"
    )
    xb, _ = bin_dataset(x, cfg.n_bins)
    w = np.asarray(
        bootstrap_counts(jax.random.PRNGKey(2), cfg.n_trees, xb.shape[0])
    ).astype(np.float32)
    yf = y.astype(np.float32)
    forest = _grow(xb, yf, w, cfg)
    r_res = np.asarray(oob_r2(forest, jnp.asarray(xb), jnp.asarray(yf), jnp.asarray(w)))
    assert np.any(r_res > 0), "fixture should have informative trees"
    for n_blocks in (4, 7):
        r_st = np.asarray(
            oob_r2_streamed(forest, np.array_split(xb, n_blocks), yf, w)
        )
        np.testing.assert_array_equal(r_st, r_res, err_msg=f"{n_blocks} blocks")


def test_train_prf_sample_block_dispatches_streamed(grow_case):
    """The public entry point: config.sample_block > 0 routes the WHOLE
    pipeline (binning, dimred, growth, OOB weights, prediction) through
    the streaming data plane, bit-identical to the resident train_prf."""
    from repro.core import train_prf

    xb, y, w, cfg = grow_case
    x, _ = make_classification(n_samples=600, n_features=13, n_classes=3, seed=3)
    cfg_imp = dataclasses.replace(cfg, feature_mode="importance")
    m_res = train_prf(x, y, cfg_imp, seed=11)
    m_st = train_prf(
        x, y, dataclasses.replace(cfg_imp, sample_block=140), seed=11
    )
    _assert_forests_equal(m_st.forest, m_res.forest, "train_prf streamed")
    np.testing.assert_array_equal(
        np.asarray(m_st.forest.tree_weight), np.asarray(m_res.forest.tree_weight)
    )
    np.testing.assert_array_equal(m_st.predict(x), m_res.predict(x))
    np.testing.assert_array_equal(m_st.predict_scores(x), m_res.predict_scores(x))


# ---------------------------------------------------------------------------
# tree_chunk remainder padding
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("tree_chunk", [4, 5])
def test_tree_chunk_remainder_is_exact(grow_case, tree_chunk):
    """n_trees=6 with tree_chunk=4/5: the final chunk is padded with
    zero-weight dummy trees instead of raising, and the forest is
    bit-identical to the unchunked run."""
    xb, y, w, cfg = grow_case
    f_c = _grow(xb, y, w, dataclasses.replace(cfg, tree_chunk=tree_chunk))
    _assert_forests_equal(f_c, _grow(xb, y, w, cfg), f"tree_chunk={tree_chunk}")


def test_chunked_level_scores_accepts_remainder(grow_case):
    """Direct call at the training/prediction-shared chunk size."""
    xb, y, w, cfg = grow_case
    cfg = dataclasses.replace(cfg, n_trees=7, tree_chunk=3)
    from repro.core.histograms import class_channels

    base = class_channels(jnp.asarray(y), cfg.n_classes)
    w7 = jnp.asarray(np.tile(w, (2, 1))[:7])
    slot = jnp.zeros((7, xb.shape[0]), jnp.int32)
    scores, n_node = chunked_level_scores(
        jnp.asarray(xb), base, w7, slot, None, cfg
    )
    cfg_full = dataclasses.replace(cfg, tree_chunk=0)
    scores_full, n_node_full = chunked_level_scores(
        jnp.asarray(xb), base, w7, slot, None, cfg_full
    )
    np.testing.assert_array_equal(np.asarray(n_node), np.asarray(n_node_full))
    # Winners and their (integer-valued) child counts are exact; the gain
    # ratio itself may move by 1 ulp — the lax.map chunk body is compiled
    # (FMA-contracted) while the single-chunk path runs op-by-op.
    for name in ("feature", "threshold", "left_counts", "right_counts"):
        np.testing.assert_array_equal(
            np.asarray(getattr(scores, name)),
            np.asarray(getattr(scores_full, name)), err_msg=name,
        )
    np.testing.assert_allclose(
        np.asarray(scores.gain_ratio), np.asarray(scores_full.gain_ratio),
        rtol=1e-6, atol=1e-6,
    )


# ---------------------------------------------------------------------------
# GrowthState — the engine's real carry
# ---------------------------------------------------------------------------


def test_growth_state_pytree_roundtrips_jit(grow_case):
    xb, y, w, cfg = grow_case
    from repro.core.histograms import class_channels

    base = class_channels(jnp.asarray(y), cfg.n_classes)
    state = init_growth_state(
        base, jnp.asarray(w), cfg, LocalPlane(), rng=jax.random.PRNGKey(7)
    )
    assert isinstance(state, GrowthState)
    out = jax.jit(lambda s: s)(state)
    assert isinstance(out, GrowthState)
    assert jax.tree_util.tree_structure(out) == jax.tree_util.tree_structure(state)
    for a, b in zip(jax.tree_util.tree_leaves(out), jax.tree_util.tree_leaves(state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert out.forest.config == cfg          # static aux survives the boundary
    assert int(out.level) == 0
    assert int(out.slot_node[0, 0]) == 0 and int(out.slot_node[0, 1]) == -1


# ---------------------------------------------------------------------------
# Mesh plane (subprocess: 8 host devices)
# ---------------------------------------------------------------------------


def test_mesh_plane_matches_local_bitwise():
    """The full plane matrix: {psum, psum_scatter} x {early-exit,
    fixed-depth} sharded growth == single-host growth, bit-for-bit,
    given identical DSI weights — plus the mesh-STREAMED driver
    (host blocks fed into the collective plane), streamed-sharded OOB,
    and streamed-sharded prediction, all bitwise against the local
    resident references."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import dataclasses
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.core import ForestConfig
        from repro.core.binning import bin_dataset
        from repro.core.distributed import (
            _grow_sharded, grow_forest_streamed,
            oob_accuracy_streamed_sharded, predict_streamed_sharded,
        )
        from repro.core.dsi import bootstrap_counts
        from repro.core.forest import grow_forest
        from repro.core.histograms import class_channels
        from repro.core.voting import oob_accuracy, predict
        from repro.data.tabular import make_classification
        from repro.launch.mesh import make_mesh

        x, y = make_classification(n_samples=640, n_features=16, n_classes=3, seed=2)
        cfg0 = ForestConfig(n_trees=6, max_depth=4, n_bins=16, n_classes=3,
                            feature_mode="all")
        xb, _ = bin_dataset(x, cfg0.n_bins)
        y_np, w_np = np.asarray(y), None
        xb_dev, y_dev = jnp.asarray(xb), jnp.asarray(y)
        w = bootstrap_counts(jax.random.PRNGKey(1), cfg0.n_trees,
                             xb.shape[0]).astype(jnp.float32)
        w_np = np.asarray(w)
        mesh = make_mesh((4, 2), ("data", "model"))
        ARRS = ("feature", "threshold", "left_child", "class_counts", "value")
        # Ragged block sizes: exercises the parked-sample padding to the
        # data-axis multiple inside the mesh-streamed driver.
        blocks = [xb[:150], xb[150:290], xb[290:500], xb[500:]]

        for hist_reduce in ("psum", "psum_scatter"):
            for early in (True, False):
                cfg = dataclasses.replace(cfg0, hist_reduce=hist_reduce,
                                          early_exit=early)
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
                f_loc = grow_forest(xb_dev, y_dev, w, cfg)
                for n in ARRS:
                    np.testing.assert_array_equal(
                        np.asarray(getattr(f_mesh, n)),
                        np.asarray(getattr(f_loc, n)),
                        err_msg=f"{n} {hist_reduce} early={early}")
            # Mesh x streaming: host blocks fed into the same collective
            # plane == the local resident forest, bit-for-bit.
            f_ms = grow_forest_streamed(
                blocks, y_np, w_np, dataclasses.replace(cfg0,
                hist_reduce=hist_reduce), mesh=mesh)
            f_loc = grow_forest(xb_dev, y_dev, w, cfg0)
            for n in ARRS:
                np.testing.assert_array_equal(
                    np.asarray(getattr(f_ms, n)), np.asarray(getattr(f_loc, n)),
                    err_msg=f"{n} streamed {hist_reduce}")
        print("MESH_STREAM_GROW_OK")

        f_loc = grow_forest(xb_dev, y_dev, w, cfg0)
        np.testing.assert_array_equal(
            np.asarray(oob_accuracy_streamed_sharded(f_loc, blocks, y_np,
                                                     w_np, mesh)),
            np.asarray(oob_accuracy(f_loc, xb_dev, y_dev, w)))
        np.testing.assert_array_equal(
            predict_streamed_sharded(f_loc, blocks, mesh),
            np.asarray(predict(f_loc, xb_dev)))
        print("MESH_PARITY_OK")
    """)
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, timeout=900,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert "MESH_PARITY_OK" in out.stdout


# ---------------------------------------------------------------------------
# Property: early-exit never changes predictions
# ---------------------------------------------------------------------------


def test_early_exit_never_changes_predictions_property():
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @given(
        seed=st.integers(0, 2 ** 10),
        depth=st.integers(2, 6),
        frontier=st.sampled_from([0, 4]),
        tree_chunk=st.sampled_from([0, 3]),
    )
    @settings(max_examples=10, deadline=None)
    def prop(seed, depth, frontier, tree_chunk):
        x, y = make_classification(
            n_samples=160, n_features=7, n_classes=3, seed=seed % 17
        )
        cfg = ForestConfig(
            n_trees=4, max_depth=depth, n_bins=8, n_classes=3,
            feature_mode="all", max_frontier=frontier, tree_chunk=tree_chunk,
            min_samples_split=8,
        )
        xb, _ = bin_dataset(x, cfg.n_bins)
        w = np.asarray(
            bootstrap_counts(jax.random.PRNGKey(seed), cfg.n_trees, xb.shape[0])
        ).astype(np.float32)
        f_ee = _grow(xb, y, w, dataclasses.replace(cfg, early_exit=True))
        f_fx = _grow(xb, y, w, dataclasses.replace(cfg, early_exit=False))
        from repro.core.voting import predict

        np.testing.assert_array_equal(
            np.asarray(predict(f_ee, jnp.asarray(xb))),
            np.asarray(predict(f_fx, jnp.asarray(xb))),
        )

    prop()
