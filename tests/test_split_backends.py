"""T_NS split-backend parity: pallas split-scan (interpret) vs XLA oracle.

The acceptance bar for the fused split-scoring kernel as the production
backend (mirrors test_hist_backends.py for T_GR):

* identical winners (feature/threshold ints) and matching gains/counts
  on the full matrix — classification/regression x feature-masked x
  all-invalid-gain slots x non-divisible F x multi-block carry;
* ``grow_forest`` builds *bit-identical* forests whichever backend
  scores the splits (integer DSI weights make histograms and their
  prefix sums exact, so only argmax order matters — and both backends
  implement first-occurrence semantics);
* the fully-fused single-host path never materializes the
  ``[tc, S, F, B, C]`` histogram in HBM (jaxpr inspection).

Float gains agree to rounding only (XLA fuses the two compiled contexts
differently), hence exact asserts on ints, allclose on floats.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ForestConfig
from repro.core.binning import bin_dataset
from repro.core.dsi import bootstrap_counts
from repro.core.forest import chunked_level_scores, grow_forest
from repro.core.gain import level_scores, resolve_split_backend
from repro.core.dimred import random_feature_mask
from repro.data.tabular import make_classification
from repro.kernels.split_scan.kernel import (
    choose_score_block, split_scan_block, split_scan_scores,
)
from repro.kernels.split_scan.ref import split_scan_ref

RNG = np.random.default_rng(11)


def _assert_scores_match(got, want, *, counts_exact=False):
    """Ints exact, floats to rounding (see module docstring)."""
    gr_g, f_g, thr_g, l_g, r_g = (np.asarray(a) for a in got)
    gr_w, f_w, thr_w, l_w, r_w = (np.asarray(a) for a in want)
    np.testing.assert_array_equal(f_g, f_w)
    np.testing.assert_array_equal(thr_g, thr_w)
    np.testing.assert_allclose(gr_g, gr_w, rtol=2e-5, atol=1e-6)
    if counts_exact:
        np.testing.assert_array_equal(l_g, l_w)
        np.testing.assert_array_equal(r_g, r_w)
    else:
        np.testing.assert_allclose(l_g, l_w, rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(r_g, r_w, rtol=1e-6, atol=1e-6)


def _random_hist(tc, s, f, b, c, *, integer=False):
    if integer:
        h = RNG.integers(0, 5, (tc, s, f, b, c)).astype(np.float32)
    else:
        h = RNG.random((tc, s, f, b, c)).astype(np.float32)
    return jnp.asarray(h)


# (tc, S, F, B, C): block-aligned and deliberately-awkward shapes.
SHAPES = [
    (2, 4, 16, 8, 3),      # aligned, single feature block
    (3, 4, 13, 8, 3),      # F non-divisible (padded + masked in-kernel)
    (1, 1, 5, 4, 2),       # tiny single slot
    (2, 2, 33, 16, 4),     # F > 8-multiple with remainder
]


@pytest.mark.parametrize("tc,s,f,b,c", SHAPES)
@pytest.mark.parametrize("masked", [False, True])
def test_kernel_matches_ref_classification(tc, s, f, b, c, masked):
    hist = _random_hist(tc, s, f, b, c)
    mask = jnp.asarray(RNG.random((tc, f)) > 0.3) if masked else None
    got = split_scan_scores(hist, mask, interpret=True)
    want = split_scan_ref(hist, mask)
    _assert_scores_match(tuple(got), want)


@pytest.mark.parametrize("tc,s,f,b,c", SHAPES[:2])
def test_kernel_matches_ref_regression(tc, s, f, b, c):
    hist = _random_hist(tc, s, f, b, 3, integer=True)
    got = split_scan_scores(hist, None, regression=True, interpret=True)
    want = split_scan_ref(hist, None, regression=True)
    _assert_scores_match(tuple(got), want, counts_exact=True)


def test_kernel_integer_counts_bit_exact():
    """Integer-valued histograms (DSI weights) -> exact child counts."""
    hist = _random_hist(3, 4, 13, 8, 3, integer=True)
    got = split_scan_scores(hist, None, interpret=True)
    _assert_scores_match(tuple(got), split_scan_ref(hist, None), counts_exact=True)


def test_kernel_multiblock_internal_carry():
    """f_blk forced below F: the in-kernel running argmax must span blocks."""
    hist = _random_hist(2, 3, 24, 8, 3)
    got = split_scan_scores(hist, None, interpret=True, f_blk=8)
    _assert_scores_match(tuple(got), split_scan_ref(hist, None))


def test_chained_carry_matches_single_shot():
    """Slab-at-a-time with a threaded carry == one pass over the full hist
    — the contract the fused T_GR->T_NS loop relies on."""
    hist = _random_hist(2, 4, 20, 8, 3, integer=True)
    mask = jnp.asarray(RNG.random((2, 20)) > 0.2)
    carry = None
    for f0 in (0, 8, 16):
        hi = min(f0 + 8, 20)
        carry = split_scan_block(
            hist[:, :, f0:hi], mask[:, f0:hi], carry, f0, interpret=True
        )
    _assert_scores_match(carry, split_scan_ref(hist, mask), counts_exact=True)


def test_all_invalid_slots_match_oracle_convention():
    """Every split empty on one side -> gain -inf, winner (f=0, thr=0)."""
    hist = jnp.zeros((2, 2, 5, 4, 3)).at[:, :, :, 0, :].set(2.0)
    got = split_scan_scores(hist, None, interpret=True)
    _assert_scores_match(tuple(got), split_scan_ref(hist, None), counts_exact=True)
    assert np.all(np.isneginf(np.asarray(got.gain_ratio)))
    assert np.all(np.asarray(got.feature) == 0)
    assert np.all(np.asarray(got.threshold) == 0)


def test_all_features_masked():
    hist = _random_hist(2, 3, 7, 8, 3, integer=True)
    mask = jnp.zeros((2, 7), jnp.bool_)
    got = split_scan_scores(hist, mask, interpret=True)
    _assert_scores_match(tuple(got), split_scan_ref(hist, mask), counts_exact=True)
    assert np.all(np.isneginf(np.asarray(got.gain_ratio)))


@pytest.mark.parametrize("regression", [False, True])
def test_level_scores_backend_dispatch(regression):
    """backend='pallas' through the public API == the xla path."""
    hist = _random_hist(3, 4, 13, 8, 3, integer=True)
    mask = jnp.asarray(RNG.random((3, 13)) > 0.3)
    sc_x, nn_x = level_scores(hist, mask, regression=regression, backend="xla")
    sc_p, nn_p = level_scores(
        hist, mask, regression=regression, backend="pallas", interpret=True
    )
    _assert_scores_match(tuple(sc_p), tuple(sc_x), counts_exact=True)
    np.testing.assert_array_equal(np.asarray(nn_p), np.asarray(nn_x))


def test_ops_wrapper_matches_oracle():
    """The jit'd public wrapper: pallas path == its own ref dispatch."""
    from repro.kernels.split_scan.ops import fused_split_scores

    hist = _random_hist(2, 3, 10, 8, 3, integer=True)
    mask = jnp.asarray(RNG.random((2, 10)) > 0.3)
    got = fused_split_scores(hist, mask, interpret=True)
    want = fused_split_scores(hist, mask, use_pallas=False)
    _assert_scores_match(tuple(got), tuple(want), counts_exact=True)


def test_resolve_split_backend():
    assert resolve_split_backend("xla") == "xla"
    assert resolve_split_backend("pallas") == "pallas"
    assert resolve_split_backend("auto") in ("pallas", "xla")
    with pytest.raises(ValueError):
        resolve_split_backend("segment_sum")


def test_choose_score_block_fits_budget():
    from repro.kernels.gain_ratio.kernel import _VMEM_BUDGET
    from repro.kernels.split_scan.kernel import score_vmem_bytes

    for (s, b, c, f) in [(64, 64, 8, 500), (1, 4, 2, 3), (16, 16, 4, 1000),
                         (256, 64, 2, 28)]:
        f_blk = choose_score_block(s, b, c, f)
        assert f_blk <= -(-min(f, 32) // 8) * 8       # never pads past one block
        if f_blk > 8:  # above the halving floor the budget MUST hold
            assert score_vmem_bytes(f_blk, s, b, c) <= _VMEM_BUDGET
            # ...and f_blk is maximal up to its caps (32, or F itself):
            # doubling it would blow the budget.
            assert (
                f_blk == 32
                or f_blk == -(-f // 8) * 8
                or score_vmem_bytes(2 * f_blk, s, b, c) > _VMEM_BUDGET
            )
    # The floor is only ever hit because even 16 features exceed the budget.
    assert choose_score_block(256, 64, 8, 500) == 8
    assert score_vmem_bytes(16, 256, 64, 8) > _VMEM_BUDGET


@pytest.mark.parametrize("masked", [False, True])
@pytest.mark.parametrize("tree_chunk", [0, 4])
def test_grow_forest_split_backend_equivalence(masked, tree_chunk):
    """Forests are bit-identical whichever backend scores the splits."""
    x, y = make_classification(n_samples=600, n_features=13, n_classes=3, seed=3)
    cfg0 = ForestConfig(
        n_trees=8, max_depth=4, n_bins=16, n_classes=3,
        feature_mode="all", tree_chunk=tree_chunk,
    )
    xb, _ = bin_dataset(x, cfg0.n_bins)
    xb, y = jnp.asarray(xb), jnp.asarray(y)
    w = bootstrap_counts(
        jax.random.PRNGKey(0), cfg0.n_trees, xb.shape[0]
    ).astype(jnp.float32)
    mask = (
        random_feature_mask(
            jax.random.PRNGKey(5), n_trees=8, n_features=13, n_selected=6
        )
        if masked
        else None
    )

    out = {}
    for be in ("xla", "pallas"):
        cfg = dataclasses.replace(cfg0, split_backend=be)
        out[be] = grow_forest(xb, y, w, cfg, mask)

    a, b = out["xla"], out["pallas"]
    np.testing.assert_array_equal(np.asarray(a.feature), np.asarray(b.feature))
    np.testing.assert_array_equal(np.asarray(a.threshold), np.asarray(b.threshold))
    np.testing.assert_array_equal(np.asarray(a.left_child), np.asarray(b.left_child))
    np.testing.assert_allclose(
        np.asarray(a.class_counts), np.asarray(b.class_counts), rtol=1e-6, atol=1e-6
    )


def _max_intermediate_size(jaxpr):
    """Largest eqn-output element count anywhere in the jaxpr (recursing
    into scan/pjit/pallas_call sub-jaxprs)."""
    m = 0
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            aval = getattr(v, "aval", None)
            if aval is not None and getattr(aval, "shape", None) is not None:
                m = max(m, int(np.prod(aval.shape)) if aval.shape else 1)
        for val in eqn.params.values():
            for sub in (val if isinstance(val, (list, tuple)) else [val]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    m = max(m, _max_intermediate_size(inner))
    return m


def test_fused_path_never_materializes_full_histogram():
    """The acceptance criterion: with split_backend='pallas' the
    single-host path holds at most one feature slab of histogram; the
    xla path (sanity check for the detector) holds the full tensor."""
    tc, S, F, B, C, N = 2, 4, 320, 16, 3, 64
    rng = np.random.default_rng(0)
    xb = jnp.asarray(rng.integers(0, B, (N, F)).astype(np.uint8))
    base = jnp.asarray(np.eye(C, dtype=np.float32)[rng.integers(0, C, N)])
    w = jnp.asarray(rng.integers(0, 3, (tc, N)).astype(np.float32))
    slot = jnp.asarray(rng.integers(-1, S, (tc, N)).astype(np.int32))

    full = tc * S * F * B * C
    sizes = {}
    for be in ("pallas", "xla"):
        cfg = ForestConfig(
            n_trees=tc, max_depth=2, n_bins=B, n_classes=C,
            max_frontier=S, feature_mode="all", split_backend=be,
        )
        jaxpr = jax.make_jaxpr(
            lambda a, b_, c, d, _cfg=cfg: chunked_level_scores(a, b_, c, d, None, _cfg)
        )(xb, base, w, slot)
        sizes[be] = _max_intermediate_size(jaxpr.jaxpr)

    assert sizes["xla"] >= full          # detector sees the full histogram
    assert sizes["pallas"] < 0.75 * full  # fused path: one slab, never the tensor
