"""Entropy / information gain / gain ratio / variable importance (paper Eq. 2-7).

All quantities are computed from **weighted class histograms** — the
TPU-native form of the paper's gain-ratio-computing tasks T_GR (§4.2.1):

    hist[t, s, f, b, c] = sum of in-bag weights of samples of tree t,
                          sitting at frontier slot s, whose feature f
                          falls in bin b, with label c.

Cumulative sums over the bin axis evaluate *every* candidate binary split
of every feature simultaneously; Eq. 2-6 then reduce those to a gain
ratio per (tree, node, feature, threshold). The only cross-device
communication this ever needs is a psum of `hist` over the sample axis
(see core/distributed.py) — the vertical-partition property.

The split-scoring stage (T_NS stage 1) has two backends, selected by
``ForestConfig.split_backend`` and dispatched by ``level_scores``:

* ``"xla"``    — the vectorized jnp path below (portable oracle);
* ``"pallas"`` — the fused split-scan kernel (``kernels/split_scan``)
  that consumes the histogram per feature block and keeps a running-best
  carry, so only O(k*S) split descriptors ever leave the kernel;
* ``"auto"``   — ``pallas`` on TPU, else ``xla``.

Both backends score *from one shared cumsum* of the histogram
(``split_gain_ratios_from_cumsum`` / ``variance_gains_from_cumsum``):
the prefix sums that produce the gain ratios are re-used for the winner's
child counts, so the bin axis is only scanned once.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

# Natural-log entropies throughout; the gain *ratio* (Eq. 6) is invariant
# to the log base as long as G and I use the same one.


def _xlogx(p: jnp.ndarray) -> jnp.ndarray:
    """x * log(x), safe at 0 (0 log 0 := 0)."""
    return jnp.where(p > 0, p * jnp.log(jnp.maximum(p, 1e-38)), 0.0)


def entropy_from_counts(counts: jnp.ndarray, axis: int = -1) -> jnp.ndarray:
    """Shannon entropy of a (possibly unnormalized) count vector. Eq. (2)."""
    total = jnp.sum(counts, axis=axis, keepdims=True)
    p = counts / jnp.maximum(total, 1e-38)
    return -jnp.sum(_xlogx(p), axis=axis)


class SplitScores(NamedTuple):
    """Per-(tree, slot) best split, after the T_NS argmax."""

    gain_ratio: jnp.ndarray    # [k, S] best gain ratio
    feature: jnp.ndarray       # [k, S] int32 best feature
    threshold: jnp.ndarray     # [k, S] int32 best bin threshold (left: bin <= thr)
    left_counts: jnp.ndarray   # [k, S, C] class counts of left child
    right_counts: jnp.ndarray  # [k, S, C] class counts of right child


def split_gain_ratios_from_cumsum(
    left: jnp.ndarray, total: jnp.ndarray, *, axis: int = -1
) -> jnp.ndarray:
    """Eq. (2)-(6) from bin prefix sums — shared by the XLA and Pallas
    split backends so their gain ratios are computed by one formula.

    Args:
      left:  bin prefix sums without the last bin: ``[..., F, B-1, C]``
             in the XLA layout (the ``cumsum`` sliced on the bin axis).
      total: node class counts, broadcastable against ``left`` along the
             bin axis (``[..., F, 1, C]``).
      axis:  the class axis of both (the split-scan kernel keeps it
             leading, ``[C, B-1, S]``).
    Returns:
      gr with the class axis reduced away (``[..., F, B-1]``); invalid
      (empty-side) splits get -inf.
    """
    n = total.sum(axis=axis)                        # [..., F, 1]
    h_node = entropy_from_counts(total, axis=axis)  # Entropy(S_i), Eq. 2

    right = total - left                            # [..., F, B-1, C]
    n_l = left.sum(axis=axis)                       # [..., F, B-1]
    n_r = right.sum(axis=axis)
    n_tot = jnp.maximum(n, 1e-38)

    # Eq. (3): conditional entropy of the target given the split.
    h_cond = (n_l / n_tot) * entropy_from_counts(left, axis=axis) + (
        n_r / n_tot
    ) * entropy_from_counts(right, axis=axis)
    gain = h_node - h_cond                          # Eq. (5)

    # Eq. (4): self-split information of the binary partition.
    p_l = n_l / n_tot
    p_r = n_r / n_tot
    split_info = -(_xlogx(p_l) + _xlogx(p_r))

    gr = gain / jnp.maximum(split_info, 1e-12)      # Eq. (6)
    valid = (n_l > 0) & (n_r > 0)
    return jnp.where(valid, gr, -jnp.inf)


def left_total(cum: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``(left, total)`` scorer operands of a ``[..., B, C]`` cumsum."""
    return cum[..., :-1, :], cum[..., -1:, :]


def split_gain_ratios(hist: jnp.ndarray) -> jnp.ndarray:
    """Gain ratio of every candidate split. Eq. (2)-(6), vectorized.

    Args:
      hist: [..., F, B, C] weighted class histograms of one node subset.
    Returns:
      gr: [..., F, B-1] gain ratio of splitting feature f at threshold b
          (left = bins 0..b). Invalid (empty-side) splits get -inf.
    """
    return split_gain_ratios_from_cumsum(*left_total(jnp.cumsum(hist, axis=-2)))


def variance_gains_from_cumsum(
    left: jnp.ndarray, total: jnp.ndarray, *, axis: int = -1
) -> jnp.ndarray:
    """Regression analogue of ``split_gain_ratios_from_cumsum``.

    Args:
      left:  ``[..., F, B-1, 3]`` prefix sums of the [count, sum, sumsq]
             channels, last bin excluded.
      total: ``[..., F, 1, 3]``.
      axis:  the channel axis of both.
    Returns: [..., F, B-1] variance reduction (invalid -> -inf).
    """

    def ch(h, i):
        return jax.lax.index_in_dim(h, i, axis, keepdims=False)

    def sse(h):
        return ch(h, 2) - ch(h, 1) * ch(h, 1) / jnp.maximum(ch(h, 0), 1e-38)

    right = total - left
    gain = sse(total) - sse(left) - sse(right)
    valid = (ch(left, 0) > 0) & (ch(right, 0) > 0)
    return jnp.where(valid, gain, -jnp.inf)


def variance_gains(sum_hist, sumsq_hist, cnt_hist):
    """Regression analogue: variance reduction per candidate split.

    Args: [..., F, B] histograms of sum(y*w), sum(y^2*w), sum(w).
    Returns: [..., F, B-1] gain (invalid -> -inf).
    """
    hist = jnp.stack([cnt_hist, sum_hist, sumsq_hist], axis=-1)
    return variance_gains_from_cumsum(*left_total(jnp.cumsum(hist, axis=-2)))


def _select_winners(gr: jnp.ndarray, cum: jnp.ndarray, total: jnp.ndarray) -> SplitScores:
    """T_NS argmax + child-count gather, re-using the scoring cumsum.

    The child counts come for free from the same prefix sums the gain
    ratios were computed from (the paper's "intermediate results
    submitted to subsequent tasks") — no second pass over the bin axis.
    """
    k, S, F, B, C = cum.shape
    flat = gr.reshape(k, S, F * (B - 1))
    best = jnp.argmax(flat, axis=-1)                # [k, S]
    best_gr = jnp.take_along_axis(flat, best[..., None], axis=-1)[..., 0]
    best_f = (best // (B - 1)).astype(jnp.int32)
    best_thr = (best % (B - 1)).astype(jnp.int32)

    f_idx = best_f[..., None, None, None]           # [k, S, 1, 1, 1]
    cum_f = jnp.take_along_axis(cum, jnp.broadcast_to(f_idx, (k, S, 1, B, C)), axis=2)[:, :, 0]
    left_counts = jnp.take_along_axis(
        cum_f, jnp.broadcast_to(best_thr[..., None, None], (k, S, 1, C)), axis=2
    )[:, :, 0]
    total_f = jnp.take_along_axis(
        total, jnp.broadcast_to(best_f[..., None, None], (k, S, 1, C)), axis=2
    )[:, :, 0]
    right_counts = total_f - left_counts
    return SplitScores(best_gr, best_f, best_thr, left_counts, right_counts)


def best_splits(hist: jnp.ndarray, feature_mask: jnp.ndarray | None = None) -> SplitScores:
    """The node-splitting task T_NS (paper Definition 4): global best split.

    Args:
      hist: [k, S, F, B, C].
      feature_mask: optional [k, F] bool — features admitted by the
        dimension-reduction step (paper Alg. 3.1). Masked-out features
        never win the argmax.
    Returns: SplitScores with [k, S] leaders + child class counts.
    """
    cum = jnp.cumsum(hist, axis=-2)                 # the ONE bin scan
    total = cum[..., -1, :]
    gr = split_gain_ratios_from_cumsum(*left_total(cum))  # [k, S, F, B-1]
    if feature_mask is not None:
        gr = jnp.where(feature_mask[:, None, :, None], gr, -jnp.inf)
    return _select_winners(gr, cum, total)


def node_counts(scores: SplitScores, *, regression: bool = False) -> jnp.ndarray:
    """Node sample count [k, S] recovered from the winner's child counts."""
    if regression:
        return scores.left_counts[..., 0] + scores.right_counts[..., 0]
    return scores.left_counts.sum(-1) + scores.right_counts.sum(-1)


def sibling_plan(
    scores: SplitScores,
    split_rank: jnp.ndarray,   # [k, S] int32 dense rank of admitted splits, -1 else
    is_split: jnp.ndarray,     # [k, S] bool
    *,
    n_ranks: int,              # R = ForestConfig.max_splits_per_level
    regression: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Plan next level's sibling-subtraction reuse (``hist_reuse``).

    For every admitted split rank r, record (a) which frontier slot is
    its parent and (b) which child is the *smaller* one — the only child
    the next level will histogram directly; the sibling is reconstructed
    as ``parent - small``. "Smaller" means fewer weighted samples, read
    off the winner's child counts the scoring cumsum already produced
    (no extra pass); ties go left. Both tables are derived from the
    post-``merge_winners`` scores, so every mesh shard plans the same
    small side.

    Returns ``(parent [k, R] int32 slot, -1 for unused ranks;
    small_right [k, R] int32, 1 = right child is the small one)``.
    """
    k, S = split_rank.shape
    R = n_ranks
    if regression:
        n_l, n_r = scores.left_counts[..., 0], scores.right_counts[..., 0]
    else:
        n_l, n_r = scores.left_counts.sum(-1), scores.right_counts.sum(-1)
    sr_slot = (n_r < n_l).astype(jnp.int32)                   # [k, S]
    # Rank -> slot scatter. Dense ranks are unique per tree; every
    # non-admitted slot dumps into the sliced-off row R.
    rank = jnp.where(is_split, split_rank, R)
    t = jnp.arange(k)[:, None]
    slots = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None, :], (k, S))
    parent = jnp.full((k, R + 1), -1, jnp.int32).at[t, rank].set(slots)[:, :R]
    small_right = (
        jnp.zeros((k, R + 1), jnp.int32).at[t, rank].set(sr_slot)[:, :R]
    )
    return parent, small_right


SPLIT_BACKENDS = ("auto", "pallas", "xla")


def resolve_split_backend(backend: str) -> str:
    """'auto' -> 'pallas' on TPU, 'xla' elsewhere."""
    if backend not in SPLIT_BACKENDS:
        raise ValueError(f"split_backend={backend!r} not in {SPLIT_BACKENDS}")
    if backend == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    return backend


def level_scores(
    hist: jnp.ndarray,
    feature_mask: jnp.ndarray | None,
    *,
    regression: bool = False,
    backend: str = "xla",
    interpret: bool | None = None,
) -> tuple[SplitScores, jnp.ndarray]:
    """T_NS stage-1: per-(tree, slot) winning split + node sample count.

    Args:
      hist: [k, S, F, B, C] (C = n_classes, or 3 regression channels).
      backend: split-scoring backend ("auto" | "pallas" | "xla"); the
        pallas backend consumes ``hist`` per feature block in VMEM and
        only the O(k*S) winners leave the kernel.
      interpret: pallas backend only; ``None`` = interpret off-TPU.
    Returns: (SplitScores, n_node [k, S]).
    """
    backend = resolve_split_backend(backend)
    if backend == "pallas":
        from ..kernels.split_scan.kernel import split_scan_scores

        scores = split_scan_scores(
            hist, feature_mask, regression=regression, interpret=interpret
        )
    elif regression:
        cum = jnp.cumsum(hist, axis=-2)
        total = cum[..., -1, :]
        gains = variance_gains_from_cumsum(*left_total(cum))
        if feature_mask is not None:
            gains = jnp.where(feature_mask[:, None, :, None], gains, -jnp.inf)
        scores = _select_winners(gains, cum, total)
    else:
        scores = best_splits(hist, feature_mask)
    return scores, node_counts(scores, regression=regression)


def multiway_gain_ratio(hist: jnp.ndarray) -> jnp.ndarray:
    """Faithful Eq. (2)-(6) with V(y_ij) = the bin values (multiway form).

    This is the quantity the paper ranks features by in Alg. 3.1: each
    distinct value of y_ij is a branch. With binned features the value
    set is the bin set.

    Args:  hist: [..., F, B, C].
    Returns: gr: [..., F].
    """
    total = hist.sum(axis=-2)                        # [..., F, C]
    n = jnp.maximum(total.sum(axis=-1), 1e-38)       # [..., F]
    h_node = entropy_from_counts(total)              # Eq. 2
    n_b = hist.sum(axis=-1)                          # [..., F, B]
    p_b = n_b / n[..., None]
    h_cond = jnp.sum(p_b * entropy_from_counts(hist), axis=-1)   # Eq. 3
    gain = h_node - h_cond                           # Eq. 5
    split_info = -jnp.sum(_xlogx(p_b), axis=-1)      # Eq. 4 (self-split info)
    return gain / jnp.maximum(split_info, 1e-12)     # Eq. 6


def variable_importance(gr: jnp.ndarray) -> jnp.ndarray:
    """Eq. (7): VI(y_ij) = GR(y_ij) / sum_a GR(y_ia), per tree.

    Args:  gr: [k, F] root-node gain ratio of each feature (clamped >= 0).
    Returns: vi: [k, F] normalized importances.
    """
    g = jnp.maximum(gr, 0.0)
    return g / jnp.maximum(g.sum(axis=-1, keepdims=True), 1e-38)
