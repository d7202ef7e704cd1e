"""Prediction-backend parity: fused traversal+voting kernel vs oracles.

The acceptance bar for the fused predict path as a production backend
(mirrors test_hist_backends.py / test_split_backends.py for training):

* kernel-vs-ref parity on the full matrix — synthetic node pools x
  non-divisible N (forced sample blocking) x non-divisible tree chunks
  chained through the resumable carry;
* trained forests predict **identical labels** whichever backend votes
  (classification/regression x hard/soft x weighted/unweighted), with
  scores matching to float rounding (the kernel accumulates trees
  sequentially, the xla path reduces over the stacked axis);
* the fused path never materializes the ``[k, N, C]`` per-tree tensor
  (jaxpr inspection);
* the OOB weight fallbacks (Eq. 8 and its R^2 analogue) are pinned:
  degenerate OOB sets get the neutral prior 0.5, never a confident
  0/0 artifact.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ForestConfig, train_prf
from repro.core.binning import apply_bins, bin_dataset
from repro.core.dsi import bootstrap_counts
from repro.core.forest import grow_forest
from repro.core.voting import (
    leaf_value_payload, leaf_vote_payload, oob_accuracy, oob_r2, predict,
    predict_regression, predict_regression_scores, predict_scores,
    resolve_predict_backend,
)
from repro.data.tabular import make_classification, make_regression, train_test_split
from repro.kernels.tree_traverse.kernel import choose_traverse_block, traverse_block
from repro.kernels.tree_traverse.ref import traverse_ref

from test_split_backends import _max_intermediate_size

RNG = np.random.default_rng(23)


def _random_pool(k, P, F, C, *, depth):
    """A random (not necessarily tree-shaped) node pool — the kernel and
    ref share the exact traversal contract, so arbitrary pools are fair."""
    feature = RNG.integers(-1, F, (k, P)).astype(np.int32)
    feature[:, 0] = RNG.integers(0, F, k)          # root always splits
    threshold = RNG.integers(0, 6, (k, P)).astype(np.int32)
    left = RNG.integers(0, max(P - 1, 1), (k, P)).astype(np.int32)
    payload = RNG.random((k, P, C)).astype(np.float32)
    return jnp.asarray(feature), jnp.asarray(threshold), jnp.asarray(left), jnp.asarray(payload)


# (k, P, F, C, N): block-aligned and deliberately-awkward shapes.
SHAPES = [
    (4, 16, 8, 3, 64),      # aligned
    (3, 23, 11, 3, 70),     # everything non-divisible
    (1, 9, 5, 1, 17),       # single tree, C=1 (regression payload shape)
    (6, 33, 7, 4, 129),     # N one past a block boundary
]


@pytest.mark.parametrize("k,p,f,c,n", SHAPES)
def test_kernel_matches_ref(k, p, f, c, n):
    depth = 5
    feat, thr, left, payload = _random_pool(k, p, f, c, depth=depth)
    xb = jnp.asarray(RNG.integers(0, 8, (n, f)).astype(np.uint8))
    got = traverse_block(xb, feat, thr, left, payload, None, depth=depth, interpret=True)
    want = traverse_ref(xb, feat, thr, left, payload, depth=depth)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6)


def test_kernel_forced_small_sample_blocks():
    """n_blk forced below N: the score tile must survive the N grid axis
    (three 128-sample blocks, the last one padded)."""
    depth = 4
    feat, thr, left, payload = _random_pool(3, 17, 6, 2, depth=depth)
    xb = jnp.asarray(RNG.integers(0, 8, (300, 6)).astype(np.uint8))
    got = traverse_block(
        xb, feat, thr, left, payload, None, depth=depth, n_blk=128, interpret=True
    )
    want = traverse_ref(xb, feat, thr, left, payload, depth=depth)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6)


def test_kernel_chained_carry_matches_single_shot():
    """Uneven tree chunks threaded through the carry == one pass —
    the contract the tree-chunked fused predict loop relies on."""
    depth = 5
    k = 7
    feat, thr, left, payload = _random_pool(k, 19, 9, 3, depth=depth)
    xb = jnp.asarray(RNG.integers(0, 8, (53, 9)).astype(np.uint8))
    carry = None
    for c0, c1 in ((0, 3), (3, 6), (6, 7)):       # deliberately non-divisible
        carry = traverse_block(
            xb, feat[c0:c1], thr[c0:c1], left[c0:c1], payload[c0:c1],
            carry, depth=depth, interpret=True,
        )
    want = traverse_ref(xb, feat, thr, left, payload, depth=depth)
    np.testing.assert_allclose(np.asarray(carry), np.asarray(want), rtol=1e-5, atol=1e-6)


def test_kernel_seeds_from_carry():
    """A nonzero carry is the starting score, exactly (psum partial-vote
    contract of the serving layer)."""
    depth = 3
    feat, thr, left, payload = _random_pool(2, 11, 5, 3, depth=depth)
    xb = jnp.asarray(RNG.integers(0, 8, (24, 5)).astype(np.uint8))
    carry = jnp.asarray(RNG.random((24, 3)).astype(np.float32))
    got = traverse_block(xb, feat, thr, left, payload, carry, depth=depth, interpret=True)
    want = traverse_ref(xb, feat, thr, left, payload, carry, depth=depth)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6)


def test_ops_wrapper_matches_oracle():
    from repro.kernels.tree_traverse.ops import fused_vote

    depth = 4
    feat, thr, left, payload = _random_pool(3, 15, 7, 2, depth=depth)
    xb = jnp.asarray(RNG.integers(0, 8, (40, 7)).astype(np.uint8))
    got = fused_vote(xb, feat, thr, left, payload, depth=depth)
    want = fused_vote(xb, feat, thr, left, payload, depth=depth, use_pallas=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6)


def test_choose_traverse_block_fits_budget():
    from repro.kernels.gain_ratio.kernel import _VMEM_BUDGET
    from repro.kernels.tree_traverse.kernel import traverse_vmem_bytes

    for (p, f, c) in [(4097, 512, 8), (26, 16, 4), (1025, 64, 2), (2049, 28, 2)]:
        n_blk = choose_traverse_block(p, f, c)
        assert n_blk % 128 == 0             # the lane axis of the score block
        if n_blk > 128:   # above the halving floor the budget MUST hold
            assert traverse_vmem_bytes(n_blk, p, f, c) <= _VMEM_BUDGET
            assert (
                n_blk == 512
                or traverse_vmem_bytes(2 * n_blk, p, f, c) > _VMEM_BUDGET
            )


# ---------------------------------------------------------------------------
# Trained-forest dispatch: labels bit-identical across backends
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def class_model():
    x, y = make_classification(n_samples=900, n_features=14, n_classes=3, seed=5)
    xtr, ytr, xte, _ = train_test_split(x, y, 0.25, 0)
    cfg = ForestConfig(
        n_trees=8, max_depth=4, n_bins=16, n_classes=3, feature_mode="all"
    )
    model = train_prf(xtr, ytr, cfg, seed=0)
    xbte = apply_bins(jnp.asarray(xte), jnp.asarray(model.bin_edges))
    return model, xbte


@pytest.mark.parametrize("soft", [False, True])
@pytest.mark.parametrize("weighted", [False, True])
def test_classification_backend_parity(class_model, soft, weighted):
    model, xbte = class_model
    cfg = dataclasses.replace(
        model.forest.config, soft_voting=soft, weighted_voting=weighted
    )
    forest = dataclasses.replace(model.forest, config=cfg)
    lx = predict(forest, xbte, backend="xla")
    lp = predict(forest, xbte, backend="pallas")
    np.testing.assert_array_equal(np.asarray(lx), np.asarray(lp))
    sx = predict_scores(forest, xbte, backend="xla")
    sp = predict_scores(forest, xbte, backend="pallas")
    np.testing.assert_allclose(np.asarray(sx), np.asarray(sp), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("weighted", [False, True])
def test_regression_backend_parity(weighted):
    x, y = make_regression(900, 10, seed=2)
    xtr, ytr, xte, _ = train_test_split(x, y, 0.25, 0)
    cfg = ForestConfig(
        n_trees=6, max_depth=4, n_bins=16, regression=True,
        feature_mode="all", weighted_voting=weighted,
    )
    model = train_prf(xtr, ytr, cfg, seed=0)
    xbte = apply_bins(jnp.asarray(xte), jnp.asarray(model.bin_edges))
    vx = predict_regression(model.forest, xbte, backend="xla")
    vp = predict_regression(model.forest, xbte, backend="pallas")
    np.testing.assert_allclose(np.asarray(vx), np.asarray(vp), rtol=1e-5, atol=1e-6)
    nx = predict_regression_scores(model.forest, xbte, backend="xla")
    np_ = predict_regression_scores(model.forest, xbte, backend="pallas")
    np.testing.assert_allclose(np.asarray(nx), np.asarray(np_), rtol=1e-5, atol=1e-6)


def test_tree_chunked_fused_predict_is_exact(class_model):
    """tree_chunk (including a non-divisible remainder: 8 = 3+3+2)
    threads the carry across pallas_calls without changing labels."""
    model, xbte = class_model
    want = predict(model.forest, xbte, backend="pallas")
    for tc in (1, 3, 4):
        cfg = dataclasses.replace(model.forest.config, tree_chunk=tc)
        forest = dataclasses.replace(model.forest, config=cfg)
        got = predict(forest, xbte, backend="pallas")
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_prfmodel_predict_bit_identical_across_backends(class_model):
    model, _ = class_model
    x, y = make_classification(n_samples=300, n_features=14, n_classes=3, seed=9)
    out = {
        be: model.with_predict_backend(be).predict(x)
        for be in ("xla", "pallas", "auto")
    }
    np.testing.assert_array_equal(out["xla"], out["pallas"])
    np.testing.assert_array_equal(out["xla"], out["auto"])


def test_resolve_predict_backend():
    assert resolve_predict_backend("xla") == "xla"
    assert resolve_predict_backend("pallas") == "pallas"
    assert resolve_predict_backend("auto") in ("pallas", "xla")
    with pytest.raises(ValueError):
        resolve_predict_backend("segment_sum")


def test_payloads_are_finite_everywhere(class_model):
    """The fused kernel's one-hot matmul reads EVERY pool row — a NaN at
    the scatter-pad slot (0/0 under XLA's subnormal flush) would poison
    the scores through 0 * NaN."""
    model, _ = class_model
    w = model.forest.tree_weight
    assert bool(jnp.isfinite(leaf_vote_payload(model.forest, w, soft=True)).all())
    assert bool(jnp.isfinite(leaf_vote_payload(model.forest, w, soft=False)).all())

    x, y = make_regression(400, 8, seed=3)
    cfg = ForestConfig(
        n_trees=4, max_depth=3, n_bins=8, regression=True, feature_mode="all"
    )
    m = train_prf(x, y, cfg, seed=0)
    assert bool(jnp.isfinite(m.forest.value).all())          # _safe_mean at work
    assert bool(jnp.isfinite(leaf_value_payload(m.forest, m.forest.tree_weight)).all())


# ---------------------------------------------------------------------------
# No [k, N, C] intermediate on the fused path (jaxpr inspection)
# ---------------------------------------------------------------------------


def test_fused_predict_never_materializes_per_tree_tensor():
    x, y = make_classification(n_samples=700, n_features=12, n_classes=4, seed=1)
    cfg = ForestConfig(
        n_trees=16, max_depth=3, n_bins=8, n_classes=4,
        max_frontier=4, feature_mode="all",
    )
    xb_np, _ = bin_dataset(x, cfg.n_bins)
    xb = jnp.asarray(xb_np)
    w = bootstrap_counts(jax.random.PRNGKey(0), cfg.n_trees, xb.shape[0]).astype(jnp.float32)
    forest = grow_forest(xb, jnp.asarray(y), w, cfg, None)

    N = 512
    xq = xb[:N]
    full = cfg.n_trees * N * cfg.n_classes
    sizes = {}
    for be in ("pallas", "xla"):
        jaxpr = jax.make_jaxpr(
            lambda a, _be=be: predict_scores(forest, a, backend=_be)
        )(xq)
        sizes[be] = _max_intermediate_size(jaxpr.jaxpr)

    assert sizes["xla"] >= full           # detector sees the per-tree tensor
    assert sizes["pallas"] < 0.75 * full  # fused path: only blocks + payload


# ---------------------------------------------------------------------------
# OOB weight fallbacks (Eq. 8 / R^2) — degenerate cases pinned
# ---------------------------------------------------------------------------


def _tiny_class_forest():
    x, y = make_classification(n_samples=200, n_features=8, n_classes=2, seed=4)
    cfg = ForestConfig(
        n_trees=4, max_depth=3, n_bins=8, n_classes=2, feature_mode="all"
    )
    xb_np, _ = bin_dataset(x, cfg.n_bins)
    xb = jnp.asarray(xb_np)
    w = bootstrap_counts(jax.random.PRNGKey(1), cfg.n_trees, xb.shape[0]).astype(jnp.float32)
    forest = grow_forest(xb, jnp.asarray(y), w, cfg, None)
    return forest, xb, jnp.asarray(y), w


def _tiny_reg_forest():
    x, y = make_regression(200, 8, seed=4)
    cfg = ForestConfig(
        n_trees=4, max_depth=3, n_bins=8, regression=True, feature_mode="all"
    )
    xb_np, _ = bin_dataset(x, cfg.n_bins)
    xb = jnp.asarray(xb_np)
    w = bootstrap_counts(jax.random.PRNGKey(1), cfg.n_trees, xb.shape[0]).astype(jnp.float32)
    y = jnp.asarray(y, jnp.float32)
    forest = grow_forest(xb, y, w, cfg, None)
    return forest, xb, y, w


def test_oob_accuracy_all_in_bag_is_neutral():
    """An all-in-bag forest (no OOB evidence at all) gets 0.5 everywhere."""
    forest, xb, y, w = _tiny_class_forest()
    all_in_bag = jnp.ones_like(w)
    np.testing.assert_array_equal(
        np.asarray(oob_accuracy(forest, xb, y, all_in_bag)), 0.5
    )


def test_oob_accuracy_single_degenerate_tree():
    forest, xb, y, w = _tiny_class_forest()
    w = w.at[2].set(jnp.ones_like(w[2]))          # tree 2: zero OOB samples
    acc = np.asarray(oob_accuracy(forest, xb, y, w))
    assert acc[2] == 0.5
    assert ((acc >= 0.0) & (acc <= 1.0)).all()


def test_oob_r2_all_in_bag_is_neutral():
    """Previously the empty-OOB 0/eps arithmetic produced a confident 1.0
    under clip; the documented fallback is the neutral prior 0.5."""
    forest, xb, y, w = _tiny_reg_forest()
    all_in_bag = jnp.ones_like(w)
    np.testing.assert_array_equal(np.asarray(oob_r2(forest, xb, y, all_in_bag)), 0.5)


def test_oob_r2_zero_variance_is_neutral():
    """Constant targets on the OOB set: R^2 undefined -> neutral 0.5,
    not a clip-masked garbage ratio."""
    forest, xb, y, w = _tiny_reg_forest()
    const_y = jnp.full_like(y, 3.25)
    r2 = np.asarray(oob_r2(forest, xb, const_y, w))
    np.testing.assert_array_equal(r2, 0.5)


def test_oob_r2_regular_case_in_unit_interval_and_finite():
    forest, xb, y, w = _tiny_reg_forest()
    r2 = np.asarray(oob_r2(forest, xb, y, w))
    assert np.isfinite(r2).all()
    assert ((r2 >= 0.0) & (r2 <= 1.0)).all()
    assert (r2 != 0.5).any()                       # real evidence used
