"""T_GR backend parity: pallas (interpret) vs segment_sum vs oracle.

The acceptance bar for the fused kernel as the production backend:
identical histograms on the full layout matrix (packed/unpacked,
classification/regression channels, non-divisible N/F, parked samples)
and identical *forests* end to end across ``hist_backend`` settings.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ForestConfig
from repro.core.binning import bin_dataset
from repro.core.dsi import bootstrap_counts
from repro.core.forest import grow_forest
from repro.core.histograms import (
    class_channels, level_histograms, regression_channels, resolve_backend,
)
from repro.data.tabular import make_classification
from repro.kernels.gain_ratio.kernel import choose_blocks, multi_tree_hist_pallas
from repro.kernels.gain_ratio.ref import level_histogram_ref

RNG = np.random.default_rng(7)


def _random_case(tc, n, f, s, b, c, channels):
    xb = RNG.integers(0, b, (n, f)).astype(np.int32)
    if channels == "classification":
        base = np.eye(c, dtype=np.float32)[RNG.integers(0, c, n)]
    else:
        base = np.asarray(regression_channels(jnp.asarray(
            RNG.standard_normal(n).astype(np.float32))))
    w = (RNG.integers(0, 4, (tc, n))).astype(np.float32)    # DSI-like counts
    slot = RNG.integers(-1, s, (tc, n)).astype(np.int32)    # incl. parked
    return jnp.asarray(xb), jnp.asarray(base), jnp.asarray(w), jnp.asarray(slot)


# (tc, N, F, S, B, C): divisible and deliberately-awkward shapes.
SHAPES = [
    (2, 256, 32, 4, 8, 3),     # block-aligned
    (3, 300, 17, 4, 8, 3),     # N and F both non-divisible
    (1, 65, 5, 1, 4, 2),       # single tree, single slot, tiny
    (4, 1030, 33, 2, 16, 4),   # N > n_blk with remainder
]


@pytest.mark.parametrize("tc,n,f,s,b,c", SHAPES)
@pytest.mark.parametrize("packed", [False, True])
def test_pallas_matches_oracles_classification(tc, n, f, s, b, c, packed):
    """The kernel against both ``segment_sum`` layouts and the oracle."""
    xb, base, w, slot = _random_case(tc, n, f, s, b, c, "classification")
    got = multi_tree_hist_pallas(
        xb, base, w, slot, n_slots=s, n_bins=b, interpret=True
    )
    want_seg = level_histograms(
        xb, base, w, slot, n_slots=s, n_bins=b, packed=packed,
        backend="segment_sum",
    )
    want_ref = level_histogram_ref(xb, base, w, slot, n_slots=s, n_bins=b)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want_seg),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want_ref),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("tc,n,f,s,b,c", SHAPES[:2])
def test_pallas_matches_oracles_regression(tc, n, f, s, b, c):
    """Regression channels [1, y, y^2] — unpacked layout only."""
    xb, base, w, slot = _random_case(tc, n, f, s, b, 3, "regression")
    got = multi_tree_hist_pallas(
        xb, base, w, slot, n_slots=s, n_bins=b, interpret=True
    )
    want = level_histograms(
        xb, base, w, slot, n_slots=s, n_bins=b, backend="segment_sum"
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_all_parked_contributes_nothing():
    xb, base, w, _ = _random_case(2, 100, 7, 3, 8, 2, "classification")
    slot = jnp.full((2, 100), -1, jnp.int32)
    got = multi_tree_hist_pallas(
        xb, base, w, slot, n_slots=3, n_bins=8, interpret=True
    )
    assert float(jnp.abs(got).max()) == 0.0


def test_level_histograms_backend_dispatch():
    """backend='pallas' through the public API == segment_sum, both packings."""
    xb, base, w, slot = _random_case(2, 300, 17, 4, 8, 3, "classification")
    for packed in (False, True):
        a = level_histograms(xb, base, w, slot, n_slots=4, n_bins=8,
                             packed=packed, backend="pallas", interpret=True)
        b = level_histograms(xb, base, w, slot, n_slots=4, n_bins=8,
                             packed=packed, backend="segment_sum")
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)


def test_resolve_backend():
    assert resolve_backend("segment_sum") == "segment_sum"
    assert resolve_backend("pallas") == "pallas"
    assert resolve_backend("auto") in ("pallas", "segment_sum")
    with pytest.raises(ValueError):
        resolve_backend("cuda")


def test_choose_blocks_fits_budget():
    from repro.kernels.gain_ratio.kernel import _VMEM_BUDGET, hist_vmem_bytes

    for (n, f, s, b, c) in [
        (10_000, 500, 64, 64, 8),
        (1_000_000, 28, 256, 64, 2),
        (1_000_000, 28, 128, 64, 2),
        (100, 3, 1, 4, 2),
    ]:
        n_blk, f_blk = choose_blocks(n, f, s, b, c)
        assert hist_vmem_bytes(n_blk, f_blk, s, b, c) <= _VMEM_BUDGET, (n_blk, f_blk)
        # TPU tiling: samples are the lane axis, features the sublane axis.
        assert n_blk % 128 == 0 and f_blk % 8 == 0
        # Never pads past one block of the real problem.
        assert n_blk <= -(-n // 128) * 128 and f_blk <= -(-f // 8) * 8


@pytest.mark.parametrize("packed", [False, True])
@pytest.mark.parametrize("tree_chunk", [0, 4])
def test_grow_forest_backend_equivalence(packed, tree_chunk):
    """Forests are identical whichever backend built the histograms."""
    x, y = make_classification(n_samples=600, n_features=13, n_classes=3, seed=3)
    cfg0 = ForestConfig(
        n_trees=8, max_depth=4, n_bins=16, n_classes=3,
        feature_mode="all", packed_hist=packed, tree_chunk=tree_chunk,
    )
    xb, _ = bin_dataset(x, cfg0.n_bins)
    xb, y = jnp.asarray(xb), jnp.asarray(y)
    w = bootstrap_counts(
        jax.random.PRNGKey(0), cfg0.n_trees, xb.shape[0]
    ).astype(jnp.float32)

    out = {}
    for be in ("segment_sum", "pallas"):
        cfg = dataclasses.replace(cfg0, hist_backend=be)
        out[be] = grow_forest(xb, y, w, cfg)

    a, b = out["segment_sum"], out["pallas"]
    np.testing.assert_array_equal(np.asarray(a.feature), np.asarray(b.feature))
    np.testing.assert_array_equal(np.asarray(a.threshold), np.asarray(b.threshold))
    np.testing.assert_array_equal(np.asarray(a.left_child), np.asarray(b.left_child))
    np.testing.assert_allclose(
        np.asarray(a.class_counts), np.asarray(b.class_counts), rtol=1e-6, atol=1e-6
    )
