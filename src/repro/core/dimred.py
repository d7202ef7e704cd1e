"""Dimension reduction in the training process (paper §3.2, Alg. 3.1).

Per tree (training subset S_i):
  1. gain ratio GR(y_ij) of every feature on the bootstrap sample (Eq. 2-6,
     multiway/faithful form over the feature's value set);
  2. variable importance VI = GR / sum(GR) (Eq. 7);
  3. keep the top ``k_imp`` features deterministically;
  4. draw ``m - k_imp`` more uniformly from the remaining ``M - k_imp``.

The result is a boolean feature mask per tree; growth never considers
masked features, reducing the effective dimensionality M -> m while
keeping the top-importance features always in play (the paper's balance
of "accuracy and diversity").
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .gain import multiway_gain_ratio, variable_importance
from .histograms import class_channels, hist_feature_slab, level_histograms
from .tracing import scope
from .types import ForestConfig


def root_gain_ratios(
    x_binned: jnp.ndarray, y: jnp.ndarray, weights: jnp.ndarray, config: ForestConfig
) -> jnp.ndarray:
    """GR(y_ij) of every feature on every tree's bootstrap sample. [k, F].

    Swept one ``hist_feature_slab``-wide feature block at a time: the
    multiway gain ratio is per-feature, so the root histogram reduces to
    [k, F] without the [k, 1, F, B, C] tensor ever existing beyond one
    slab (same discipline as ``forest.fused_level_scores``).
    """
    k, N = weights.shape
    F = x_binned.shape[1]
    B = config.n_bins
    base = class_channels(y, config.n_classes)
    slot0 = jnp.zeros((k, N), jnp.int32)
    W = hist_feature_slab(N, F, 1, B, config.n_classes)

    def slab_gr(xb_s):                                   # [N, W] -> [k, W]
        hist = level_histograms(
            xb_s, base, weights, slot0, n_slots=1, n_bins=B,
            backend=config.hist_backend,
        )                                                # [k, 1, W, B, C]
        return multiway_gain_ratio(hist[:, 0])

    if W >= F:
        return slab_gr(x_binned)                         # single slab
    from ..kernels.gain_ratio.kernel import _round_up

    Fp = _round_up(F, W)
    xb = jnp.pad(x_binned, ((0, 0), (0, Fp - F)))
    gr = jax.lax.map(
        lambda j: slab_gr(jax.lax.dynamic_slice_in_dim(xb, j * W, W, axis=1)),
        jnp.arange(Fp // W),
    )                                                    # [Fp/W, k, W]
    return jnp.moveaxis(gr, 0, 1).reshape(k, Fp)[:, :F]


@partial(jax.jit, static_argnames=("n_selected", "n_important"))
@scope("dimred")
def select_features(
    gr: jnp.ndarray, rng: jax.Array, *, n_selected: int, n_important: int
) -> jnp.ndarray:
    """Alg. 3.1 steps 10-19: top-k_imp by VI + uniform (m - k_imp) of the rest.

    Args:  gr [k, F].  Returns: mask [k, F] bool with exactly m True per tree.
    """
    k, F = gr.shape
    vi = variable_importance(gr)                          # Eq. (7)
    # Deterministic top-k_imp: rank by VI (desc).
    vi_rank = jnp.argsort(jnp.argsort(-vi, axis=-1), axis=-1)   # rank of each feature
    top_mask = vi_rank < n_important

    # Uniform (m - k_imp) of the remainder: random keys, masked ranking.
    u = jax.random.uniform(rng, (k, F))
    u = jnp.where(top_mask, -jnp.inf, u)                  # exclude the top features
    u_rank = jnp.argsort(jnp.argsort(-u, axis=-1), axis=-1)
    rest_mask = u_rank < (n_selected - n_important)
    return top_mask | rest_mask


@partial(jax.jit, static_argnames=("n_trees", "n_features", "n_selected"))
@scope("dimred")
def random_feature_mask(
    rng: jax.Array, *, n_trees: int, n_features: int, n_selected: int
) -> jnp.ndarray:
    """Breiman-RF feature selection (paper §3.1 step 2): m uniform per tree."""
    u = jax.random.uniform(rng, (n_trees, n_features))
    rank = jnp.argsort(jnp.argsort(-u, axis=-1), axis=-1)
    return rank < n_selected


def dimension_reduction(
    x_binned: jnp.ndarray,
    y: jnp.ndarray,
    weights: jnp.ndarray,
    config: ForestConfig,
    rng: jax.Array,
) -> jnp.ndarray:
    """Full Alg. 3.1. Returns per-tree feature mask [k, F]."""
    cfg = config.resolved(x_binned.shape[1])
    gr = root_gain_ratios(x_binned, y, weights, cfg)
    return select_features(
        gr, rng, n_selected=cfg.n_selected, n_important=cfg.n_important
    )


@partial(jax.jit, static_argnames=("n_bins", "backend"))
@scope("dimred")
def _root_hist_block(hist_acc, xb_b, base_b, w_b, *, n_bins, backend):
    slot0 = jnp.zeros_like(w_b, dtype=jnp.int32)
    return hist_acc + level_histograms(
        xb_b, base_b, w_b, slot0, n_slots=1, n_bins=n_bins, backend=backend,
    )


def dimension_reduction_streamed(
    x_binned,
    y: jnp.ndarray,
    weights: jnp.ndarray,
    config: ForestConfig,
    rng: jax.Array,
    *,
    prefetch: int = 2,
) -> jnp.ndarray:
    """Alg. 3.1 over host sample blocks (the streaming data plane).

    The root histogram is a sum over samples, so it accumulates block by
    block exactly like the growth histograms — DSI counts are integer-
    valued, the accumulation is bit-exact, and the resulting mask equals
    the resident ``dimension_reduction`` mask bitwise (the gain ratio is
    per-feature, so full-F scoring of the accumulated histogram matches
    the resident slab sweep). The sweep's own working set is one block,
    its [k, Nb] weight slice, and the [k, 1, F, B, C] root histogram —
    the [N, F] matrix is never device-resident (the caller's [k, N]
    DSI weights are, as everywhere on the streaming plane).
    """
    from ..data.pipeline import BlockFeeder, stream_blocks

    y_np = np.asarray(y)
    w_np = np.asarray(weights, dtype=np.float32)
    blocks = stream_blocks(
        x_binned, config.sample_block, what="dimension_reduction_streamed",
        n_y=y_np.shape[0], n_w=w_np.shape[1],
    )
    feeder = BlockFeeder(blocks, prefetch=prefetch)
    F = feeder.blocks[0].shape[1]
    cfg = config.resolved(F)
    k = weights.shape[0]
    hist = jnp.zeros((k, 1, F, cfg.n_bins, cfg.n_classes), jnp.float32)
    o = 0
    for xb_b in feeder.sweep():
        n = xb_b.shape[0]
        base_b = class_channels(feeder.pin(y_np[o:o + n]), cfg.n_classes)
        hist = _root_hist_block(
            hist, xb_b, base_b, feeder.pin(w_np[:, o:o + n]),
            n_bins=cfg.n_bins, backend=cfg.hist_backend,
        )
        o += n
    gr = multiway_gain_ratio(hist[:, 0])                 # [k, F]
    return select_features(
        gr, rng, n_selected=cfg.n_selected, n_important=cfg.n_important
    )
