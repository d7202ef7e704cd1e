"""PRF end-to-end behaviour: growth, prediction, voting, dimred, baselines."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ForestConfig, train_prf
from repro.core.baselines import data_volume_bytes, train_mlrf_like, train_rf
from repro.data.tabular import make_classification, make_regression, train_test_split


def test_prf_beats_majority_baseline(class_data):
    xtr, ytr, xte, yte = class_data
    cfg = ForestConfig(n_trees=16, max_depth=6, n_bins=32, n_classes=4)
    model = train_prf(xtr, ytr, cfg, seed=0)
    acc = model.accuracy(xte, yte)
    maj = np.bincount(yte).max() / len(yte)
    assert acc > maj + 0.25, (acc, maj)
    assert acc > 0.75


def test_tree_chunking_is_exact(class_data):
    xtr, ytr, xte, yte = class_data
    cfg = ForestConfig(n_trees=8, max_depth=5, n_bins=16, n_classes=4)
    m1 = train_prf(xtr, ytr, cfg, seed=3)
    m2 = train_prf(xtr, ytr, dataclasses.replace(cfg, tree_chunk=2), seed=3)
    np.testing.assert_array_equal(
        np.asarray(m1.forest.feature), np.asarray(m2.forest.feature)
    )
    np.testing.assert_array_equal(
        np.asarray(m1.forest.threshold), np.asarray(m2.forest.threshold)
    )


def test_beam_frontier_bounds_nodes(class_data):
    xtr, ytr, xte, yte = class_data
    cfg = ForestConfig(
        n_trees=4, max_depth=10, n_bins=16, n_classes=4, max_frontier=8
    )
    m = train_prf(xtr, ytr, cfg, seed=0)
    assert m.forest.feature.shape[1] == cfg.max_nodes + 1
    assert m.accuracy(xte, yte) > 0.6


def test_oob_weights_in_unit_interval(class_data):
    xtr, ytr, _, _ = class_data
    cfg = ForestConfig(n_trees=8, max_depth=5, n_bins=16, n_classes=4)
    m = train_prf(xtr, ytr, cfg, seed=1)
    w = np.asarray(m.forest.tree_weight)
    assert ((w >= 0) & (w <= 1)).all()
    assert w.std() > 0  # trees genuinely differ


def test_weighted_voting_improves_on_noisy_data():
    x, y = make_classification(
        n_samples=4000, n_features=120, n_classes=3, n_informative=8,
        label_noise=0.2, seed=11,
    )
    xtr, ytr, xte, yte = train_test_split(x, y, 0.25, 0)
    base = ForestConfig(n_trees=24, max_depth=6, n_bins=16, n_classes=3)
    accs_w, accs_p = [], []
    for s in range(3):
        accs_w.append(train_prf(xtr, ytr, base, seed=s).accuracy(xte, yte))
        accs_p.append(
            train_prf(
                xtr, ytr, dataclasses.replace(base, weighted_voting=False), seed=s
            ).accuracy(xte, yte)
        )
    assert np.mean(accs_w) >= np.mean(accs_p) - 0.01   # weighting never hurts


def test_prf_beats_rf_in_high_dim_regime():
    """The paper's headline claim (Figs. 8-9): importance-guided dimension
    reduction beats random-subspace RF on high-dimensional noisy data."""
    x, y = make_classification(
        n_samples=3000, n_features=800, n_classes=3, n_informative=8,
        n_redundant=4, label_noise=0.1, class_sep=1.2, seed=7,
    )
    xtr, ytr, xte, yte = train_test_split(x, y, 0.25, 0)
    cfg = ForestConfig(n_trees=16, max_depth=6, n_bins=16, n_classes=3)
    acc_prf = train_prf(xtr, ytr, cfg, seed=0).accuracy(xte, yte)
    acc_rf = train_rf(xtr, ytr, cfg, seed=0).accuracy(xte, yte)
    assert acc_prf > acc_rf + 0.1, (acc_prf, acc_rf)


def test_mlrf_sampling_degrades_with_small_budget(class_data):
    xtr, ytr, xte, yte = class_data
    cfg = ForestConfig(n_trees=16, max_depth=6, n_bins=32, n_classes=4)
    acc_big = train_mlrf_like(xtr, ytr, cfg, seed=0, sample_budget=2000).accuracy(xte, yte)
    acc_tiny = train_mlrf_like(xtr, ytr, cfg, seed=0, sample_budget=40).accuracy(xte, yte)
    assert acc_big >= acc_tiny - 0.02


def test_regression_r2():
    x, y = make_regression(3000, 32, seed=5)
    xtr, ytr, xte, yte = train_test_split(x, y, 0.25, 0)
    cfg = ForestConfig(
        n_trees=16, max_depth=6, n_bins=32, regression=True, feature_mode="all"
    )
    m = train_prf(xtr, ytr, cfg, seed=0)
    pred = m.predict(xte)
    r2 = 1 - np.mean((pred - yte) ** 2) / np.var(yte)
    assert r2 > 0.6


def test_data_volume_model_flat_in_k():
    """Fig. 14: PRF volume ~flat in ensemble scale, RF linear."""
    N, M = 100_000, 1000
    v_rf_10 = data_volume_bytes("rf", N, M, 10)
    v_rf_100 = data_volume_bytes("rf", N, M, 100)
    assert v_rf_100 == 10 * v_rf_10                      # linear in k
    v_paper_10 = data_volume_bytes("prf-paper", N, M, 10)
    v_paper_100 = data_volume_bytes("prf-paper", N, M, 100)
    assert v_paper_100 == v_paper_10                     # exactly flat (2NM)
    v_prf_10 = data_volume_bytes("prf-tpu", N, M, 10)
    v_prf_100 = data_volume_bytes("prf-tpu", N, M, 100)
    assert v_prf_100 < 2 * v_prf_10                      # k*N counts only
    assert v_prf_100 < v_rf_100 / 100                    # orders smaller than RF


# ---------------------------------------------------------------------------
# OOB leaves carried by the growth loop
# ---------------------------------------------------------------------------


LEAF_CASES = {
    "reuse_on": dict(hist_reuse="on"),
    "reuse_off": dict(hist_reuse="off"),
    "regression": dict(regression=True),
    "depth2": dict(max_depth=2),
    "depth8": dict(max_depth=8),
    "fixed_depth": dict(early_exit=False),
    "stops_early": dict(max_depth=7, min_samples_split=100),
    "stops_early_fixed_depth": dict(
        max_depth=7, min_samples_split=100, early_exit=False
    ),
}


@pytest.mark.parametrize("case", list(LEAF_CASES))
def test_grown_leaves_equal_the_walk(case):
    """The leaf the growth loop carries for every row equals the leaf
    ``route_to_leaves`` finds by walking the finished forest."""
    from repro.core.binning import bin_dataset
    from repro.core.dsi import bootstrap_counts
    from repro.core.forest import grow_forest, grown_leaves, route_to_leaves

    kw = {"n_trees": 6, "max_depth": 4, "n_bins": 16, **LEAF_CASES[case]}
    if kw.get("regression"):
        x, y = make_regression(600, 13, seed=3)
        y = y.astype(np.float32)
        cfg = ForestConfig(feature_mode="all", **kw)
    else:
        x, y = make_classification(n_samples=600, n_features=13, n_classes=3, seed=3)
        cfg = ForestConfig(n_classes=3, **kw)
    cfg = cfg.resolved(x.shape[1])
    xb, _ = bin_dataset(x, cfg.n_bins)
    xb = jnp.asarray(xb)
    w = bootstrap_counts(jax.random.PRNGKey(0), cfg.n_trees, xb.shape[0])
    forest = grow_forest(xb, jnp.asarray(y), w, cfg)
    leaves = np.asarray(grown_leaves(forest, xb))
    np.testing.assert_array_equal(leaves, np.asarray(route_to_leaves(forest, xb)))
    # Every leaf is a node the forest did not split.
    assert (np.take_along_axis(np.asarray(forest.feature), leaves, 1) < 0).all()
    if case.startswith("stops_early"):
        last_band = 1 + 2 * cfg.max_splits_per_level * (cfg.max_depth - 1)
        assert (leaves.max(axis=1) < last_band).any()   # a tree stopped early
    assert grown_leaves(forest, jnp.array(xb)) is None   # other rows: walk


def _oob_calls(monkeypatch, name):
    """Spy on ``api.<name>``: the OOB scorer ``train_prf`` calls."""
    from repro.core import api

    calls, real = [], getattr(api, name)

    def spy(forest, xb, y, w):
        calls.append((forest, xb, y, w))
        return real(forest, xb, y, w)

    monkeypatch.setattr(api, name, spy)
    return calls


def _walk_forbidden(monkeypatch):
    from repro.core import forest, voting

    def walk(*a, **k):
        raise AssertionError("route_to_leaves ran on the resident path")

    monkeypatch.setattr(forest, "route_to_leaves", walk)
    monkeypatch.setattr(voting, "route_to_leaves", walk)


WEIGHT_CASES = ("classification", "regression", "sanitized_labels")


@pytest.mark.parametrize("case", WEIGHT_CASES)
def test_tree_weight_equals_the_walk_bitwise(monkeypatch, case):
    """``train_prf`` scores Eq. 8 at the growth loop's leaves, runs no
    walk, and its weights are bitwise those of the walk-based scorer on
    the same rows; the model keeps no leaves."""
    from repro.core import voting
    from repro.core.forest import grown_leaves

    if case == "regression":
        x, y = make_regression(800, 12, seed=4)
        cfg = ForestConfig(n_trees=6, max_depth=5, n_bins=16, regression=True,
                           feature_mode="all")
        scorer = "oob_r2"
    else:
        x, y = make_classification(n_samples=800, n_features=12, n_classes=3, seed=4)
        cfg = ForestConfig(n_trees=6, max_depth=5, n_bins=16, n_classes=3)
        scorer = "oob_accuracy"
    policy = "raise"
    if case == "sanitized_labels":
        y = np.array(y)
        y[5:40] = 7                            # out of range: imputed, not scored
        policy = "sanitize"
    calls = _oob_calls(monkeypatch, scorer)
    with monkeypatch.context() as m:
        _walk_forbidden(m)
        model = train_prf(x, y, cfg, seed=2, bad_block_policy=policy)
    (forest, xb, y_o, w_o), = calls
    assert grown_leaves(forest, xb) is not None
    if case == "sanitized_labels":
        assert xb.shape[0] == x.shape[0] - 35
        assert model.quarantine.sanitized_labels == 35
    walked = getattr(voting, scorer)(dataclasses.replace(forest), xb, y_o, w_o)
    np.testing.assert_array_equal(
        np.asarray(model.forest.tree_weight), np.asarray(walked)
    )
    assert getattr(model.forest, "_leaves_of", None) is None
