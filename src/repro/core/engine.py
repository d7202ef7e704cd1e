"""Unified task-DAG growth engine (paper §4.2) — ONE level-step for every
execution plane.

The paper's schedulers dispatch only the T_GR/T_NS tasks that actually
exist; here that DAG is a single level-step implementation, threaded as
a real ``GrowthState`` carry and parameterized by a **collective plane**:

* ``combine_hist``    — T_GR combine of per-shard histograms (``None``
                        on the single-host plane, which unlocks the
                        fused no-HBM-histogram path; ``psum`` /
                        ``psum_scatter`` on the mesh plane);
* ``merge_winners``   — T_NS cross-shard argmax merge of the per-shard
                        split leaders (identity locally);
* ``broadcast_route`` — the per-sample go-left/right bit (a local
                        gather+compare, plus a masked ``psum`` over the
                        feature axis when features are sharded).

``forest.grow_forest`` (LocalPlane), ``distributed._grow_sharded``
(MeshPlane, built in core/distributed.py next to its collectives) and
the host-streaming ``distributed.grow_forest_streamed`` driver are thin
entry points over the same ``plan_level`` / ``write_level`` /
``route_level`` pieces, so a split decision is computed by exactly one
piece of code no matter where the data lives.

Scheduling upgrades over the fixed-depth scan of the original trainers:

* **early-exit** (``ForestConfig.early_exit``) — ``grow`` runs a
  ``lax.while_loop`` that stops as soon as every tree's frontier is
  empty, and trees whose frontiers died earlier contribute zero-weight
  (masked) work inside each ``tree_chunk`` task group;
* **sample-block streaming** (``ForestConfig.sample_block``) — level
  histograms accumulate over ``[Nb, F]`` row blocks (the resumable
  T_GR carry, ``histograms.blocked_level_histograms``), mirroring
  ``fused_vote_scores``' chunk carry on the predict side.

Every path stays bit-identical where semantics are unchanged: the pad
slot is sanitized after growth (``finalize_forest``), so
{local, mesh} x {early-exit, fixed-depth} x {streamed, resident}
produce identical ``Forest`` arrays (tests/test_engine.py).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .gain import (
    SplitScores,
    level_scores,
    node_counts,
    resolve_split_backend,
    sibling_plan,
)
from .histograms import (
    blocked_level_histograms,
    hist_feature_slab,
    level_histograms,
    sibling_expand,
    sibling_perm,
    sibling_segments,
)
from ..kernels import default_interpret
from .tracing import scope
from .types import Forest, ForestConfig, GrowthState


def init_forest(config: ForestConfig) -> Forest:
    k, P = config.n_trees, config.max_nodes + 1  # +1 pad slot
    C = 3 if config.regression else config.n_classes
    return Forest(
        feature=jnp.full((k, P), -1, jnp.int32),
        threshold=jnp.zeros((k, P), jnp.int32),
        left_child=jnp.full((k, P), -1, jnp.int32),
        class_counts=jnp.zeros((k, P, C), jnp.float32),
        value=jnp.zeros((k, P), jnp.float32),
        tree_weight=jnp.ones((k,), jnp.float32),
        config=config,
    )


def _safe_mean(counts: jnp.ndarray) -> jnp.ndarray:
    """Weighted mean ``sum / count`` of [..., C>=2] regression channels,
    0 when the count is 0.

    ``sum / maximum(count, 1e-38)`` is NOT safe here: 1e-38 is a
    subnormal float32, which XLA flushes to zero on CPU/TPU, so
    zero-count slots (every non-split frontier slot writes the pad
    node) silently became 0/0 = NaN. Harmless to the gather-based
    predict path (the pad slot is unreachable), but the fused traversal
    kernel reads every pool row through a one-hot matmul and 0 * NaN
    poisons the scores.
    """
    return jnp.where(
        counts[..., 0] > 0,
        counts[..., 1] / jnp.maximum(counts[..., 0], 1e-38),
        0.0,
    )


def _gather_feature_bins(xb: jnp.ndarray, f: jnp.ndarray) -> jnp.ndarray:
    """bins[t, i] = xb[i, f[t, i]] as ONE flattened gather.

    Replaces the per-tree ``vmap(take_along_axis)`` that re-materialized
    a [k, N] int32 gather per call site per level: broadcasting the row
    index over the tree axis lowers to a single gather of [k, N] pairs.
    """
    return xb.astype(jnp.int32)[jnp.arange(xb.shape[0])[None, :], f]


def _rank_splits(gain: jnp.ndarray, valid: jnp.ndarray, n_max: int) -> jnp.ndarray:
    """Beam selection: rank valid slots by gain, admit top n_max.

    Returns split_rank [k, S] int32 in [0, n_max) for admitted slots, -1 else.
    """
    score = jnp.where(valid, gain, -jnp.inf)
    order = jnp.argsort(-score, axis=-1)
    pos = jnp.argsort(order, axis=-1).astype(jnp.int32)        # rank of each slot
    admitted = valid & (pos < n_max)
    return jnp.where(admitted, pos, -1)


# ---------------------------------------------------------------------------
# Collective planes
# ---------------------------------------------------------------------------


class CollectivePlane:
    """The engine's collective protocol — identity ops on a single host.

    A plane answers the three questions the level-step cannot answer
    locally: how per-shard histograms combine (``combine_hist``), how
    per-shard split leaders merge (``merge_winners``), and how the
    winning feature's go-right bit reaches every sample shard
    (``broadcast_route``). ``reduce_root`` combines the root class
    counts once, before the level loop. ``level_mask`` is the feature
    mask as this plane's histogram consumer expects it (the
    reduce-scatter mesh plane scores a narrower post-scatter slice).

    The mesh implementation (``distributed.MeshPlane``) lives next to
    its collectives in core/distributed.py.
    """

    combine_hist = None          # Optional[Callable]; None => no combine,
    level_mask = None            # which unlocks the fused single-host path

    def reduce_root(self, root_counts: jnp.ndarray) -> jnp.ndarray:
        return root_counts

    def merge_winners(self, scores: SplitScores, n_node: jnp.ndarray):
        return scores, n_node

    def broadcast_route(self, x_binned, f_i, thr_i) -> jnp.ndarray:
        bins_i = _gather_feature_bins(x_binned, f_i)
        return (bins_i > thr_i).astype(jnp.int32)

    def hist_width(self, n_features: int) -> int:
        """Feature width of a post-``combine_hist`` histogram on this
        plane — what the ``hist_reuse`` cache must allocate. The local
        shard's full width here; the reduce-scatter mesh plane keeps
        only its post-scatter feature slice."""
        return n_features


class LocalPlane(CollectivePlane):
    """Single-host plane: the whole ``[N, F]`` block lives on one device."""

    def __init__(self, feature_mask: Optional[jnp.ndarray] = None):
        self.level_mask = feature_mask


# ---------------------------------------------------------------------------
# T_GR + T_NS stage 1: histogram -> score, chunked over the tree axis
# ---------------------------------------------------------------------------


@scope("tgr")
def _level_hists(
    x_binned, base_channels, w_c, slot_c, config: ForestConfig,
    n_slots: Optional[int] = None,
):
    """One chunk's level histogram, blocked over samples when
    ``config.sample_block`` asks for it. ``n_slots`` overrides the
    frontier width (the sibling-subtraction reuse path histograms into
    ``max_splits_per_level`` *rank* segments instead of slots)."""
    packed = config.packed_hist and not config.regression
    S = config.frontier if n_slots is None else n_slots
    if config.sample_block > 0:
        return blocked_level_histograms(
            x_binned, base_channels, w_c, slot_c,
            n_slots=S, n_bins=config.n_bins,
            sample_block=config.sample_block, packed=packed,
            backend=config.hist_backend,
        )
    return level_histograms(
        x_binned, base_channels, w_c, slot_c,
        n_slots=S, n_bins=config.n_bins, packed=packed,
        backend=config.hist_backend,
    )


def fused_level_scores(
    x_binned: jnp.ndarray,       # [N, F] uint8
    base_channels: jnp.ndarray,  # [N, C]
    weights: jnp.ndarray,        # [tc, N]
    sample_slot: jnp.ndarray,    # [tc, N]
    feature_mask: Optional[jnp.ndarray],  # [tc, F] bool or None
    config: ForestConfig,
):
    """Fully-fused T_GR -> T_NS: histogram kernel -> split-scan kernel
    per feature slab; the ``[tc, S, F, B, C]`` histogram never exists in
    HBM. Peak histogram footprint is one ``[tc, S, W, B, C]`` slab,
    where ``W = hist_feature_slab(...)`` is the hist kernel's own
    feature block — so per-slab pallas histograms are bit-identical to
    slices of the unfused call, and so are the resulting forests.

    The T_NS argmax rides along as the split-scan kernel's running-best
    carry, threaded through the slab loop; only O(tc*S) descriptors
    survive. With ``config.sample_block > 0`` each slab additionally
    accumulates its histogram over sample blocks, composing the two
    resumable carries. Returns (SplitScores, n_node [tc, S]).
    """
    from ..kernels.gain_ratio.kernel import _round_up
    from ..kernels.split_scan.kernel import init_carry, split_scan_block

    tc = weights.shape[0]
    N, F = x_binned.shape
    S, B = config.frontier, config.n_bins
    C = base_channels.shape[-1]
    W = hist_feature_slab(N, F, S, B, C)
    Fp = _round_up(F, W)
    xb = jnp.pad(x_binned, ((0, 0), (0, Fp - F)))
    mask = (
        feature_mask if feature_mask is not None else jnp.ones((tc, F), jnp.bool_)
    )
    mask = jnp.pad(mask, ((0, 0), (0, Fp - F)))   # padded features masked out
    interpret = default_interpret()

    def slab(j, carry):
        f0 = j * W
        xb_s = jax.lax.dynamic_slice_in_dim(xb, f0, W, axis=1)
        mask_s = jax.lax.dynamic_slice_in_dim(mask, f0, W, axis=1)
        hist = _level_hists(xb_s, base_channels, weights, sample_slot, config)
        with scope("tns"):
            return split_scan_block(
                hist, mask_s, carry, f0,
                regression=config.regression, interpret=interpret,
            )

    carry = jax.lax.fori_loop(0, Fp // W, slab, init_carry(tc, S, C))
    scores = SplitScores(*carry)
    return scores, node_counts(scores, regression=config.regression)


def chunked_level_scores(
    x_binned: jnp.ndarray,       # [N, F] uint8 (local shard in distributed mode)
    base_channels: jnp.ndarray,  # [N, C]
    weights: jnp.ndarray,        # [k, N]
    sample_slot: jnp.ndarray,    # [k, N]
    feature_mask: Optional[jnp.ndarray],  # [k, F] bool or None
    config: ForestConfig,
    *,
    hist_reduce=None,            # optional fn(hist) -> hist (e.g. psum over 'data')
):
    """T_GR + T_NS-stage-1 for all k trees, chunked over the tree axis.

    The histogram tensor only ever exists for ``tree_chunk`` trees at a
    time; only the O(k*S) split descriptors survive the chunk loop.
    With ``split_backend="pallas"`` on the single-host path
    (``hist_reduce is None``) the chunk runs ``fused_level_scores`` and
    the histogram never exists at all beyond one feature slab; the
    distributed path still combines full feature-shard histograms
    (psum / psum_scatter) and applies the fused scorer post-combine.

    ``n_trees`` need not divide ``tree_chunk``: the final chunk is
    padded with zero-weight, all-parked, no-feature dummy trees (the
    same remainder handling ``fused_vote_scores`` applies on the
    predict side) and the pad rows are sliced off the result, so
    training and prediction accept the same chunk sizes.

    Returns (SplitScores [k, S, ...], n_node [k, S]).
    """
    k = config.n_trees
    S = config.frontier
    tc = config.tree_chunk if config.tree_chunk > 0 else k
    tc = min(tc, k)

    split_be = resolve_split_backend(config.split_backend)

    def score_chunk(w_c, slot_c, mask_c):
        if hist_reduce is None and split_be == "pallas":
            return fused_level_scores(
                x_binned, base_channels, w_c, slot_c, mask_c, config
            )
        hist = _level_hists(x_binned, base_channels, w_c, slot_c, config)
        if hist_reduce is not None:
            hist = hist_reduce(hist)     # psum over the sample axis (T_GR combine)
        with scope("tns"):
            return level_scores(
                hist, mask_c, regression=config.regression, backend=split_be
            )

    if tc >= k:
        return score_chunk(weights, sample_slot, feature_mask)

    # NOTE: the mask's feature dim may be narrower than x_binned's when
    # the histogram reduce scatters features (psum_scatter path).
    mask = (
        feature_mask
        if feature_mask is not None
        else jnp.ones((k, x_binned.shape[1]), jnp.bool_)
    )
    kp = -(-k // tc) * tc
    if kp != k:                  # pad the remainder chunk with dummy trees
        weights = jnp.pad(weights, ((0, kp - k), (0, 0)))
        sample_slot = jnp.pad(
            sample_slot, ((0, kp - k), (0, 0)), constant_values=-1
        )
        mask = jnp.pad(mask, ((0, kp - k), (0, 0)))
    nc = kp // tc
    scores, n_node = jax.lax.map(
        lambda args: score_chunk(*args),
        (
            weights.reshape(nc, tc, -1),
            sample_slot.reshape(nc, tc, -1),
            mask.reshape(nc, tc, mask.shape[-1]),
        ),
    )
    scores = jax.tree_util.tree_map(
        lambda a: a.reshape(kp, *a.shape[2:])[:k], scores
    )
    return scores, n_node.reshape(kp, S)[:k]


# ---------------------------------------------------------------------------
# Sibling-subtraction histogram reuse (ForestConfig.hist_reuse)
# ---------------------------------------------------------------------------


def resolve_hist_reuse(config: ForestConfig, n_features: int) -> bool:
    """Whether growth should carry the between-level histogram cache.

    ``resolved_hist_reuse()`` answers the policy question (auto ->
    classification only); this adds the capacity gate: the cache is one
    ``[k, S, F, B, C]`` f32 tensor pinned across the whole growth, so if
    ``4*k*S*F*B*C`` exceeds ``hist_reuse_budget_mb`` the engine falls
    back to ``off`` rather than OOM a device. ``n_features`` is the
    width this plane would cache (the local shard width on a mesh — the
    budget is per-device, and identical on every shard).
    """
    if config.resolved_hist_reuse() == "off":
        return False
    C = 3 if config.regression else config.n_classes
    cache_bytes = 4 * config.n_trees * config.frontier * n_features * config.n_bins * C
    return cache_bytes <= config.hist_reuse_budget_mb * (1 << 20)


def init_hist_cache(config: ForestConfig, hist_width: int) -> dict:
    """Level-0 reuse cache. ``small_right = 0`` makes slot 0 the "small"
    child of rank 0, so the root histogram falls out of the same packed
    path with no special case: every sample (slot 0) lands in rank
    segment 0, and the all-(-1) ``parent`` table zeroes every
    subtraction row against the zero ``hist``."""
    k, S, R = config.n_trees, config.frontier, config.max_splits_per_level
    C = 3 if config.regression else config.n_classes
    return {
        "hist": jnp.zeros((k, S, hist_width, config.n_bins, C), jnp.float32),
        "perm": jnp.tile(jnp.arange(S, dtype=jnp.int32)[None, :], (k, 1)),
        "parent": jnp.full((k, R), -1, jnp.int32),
        "small_right": jnp.zeros((k, R), jnp.int32),
    }


def fused_reuse_level_scores(
    x_binned, base_channels, weights, seg, feature_mask, cache,
    config: ForestConfig,
):
    """Reuse-mode analogue of ``fused_level_scores``: per feature slab,
    build the *packed* small-child histogram (R rank rows — half the
    one-hot matmul width of the off path), expand it against the cached
    slab (``parent - small``), feed the expanded slab to the split-scan
    carry, and write it into the next cache tensor. The full-width
    cache lives in HBM (that is exactly what ``hist_reuse_budget_mb``
    budgets); the *working set* stays one ``[k, S, W, B, C]`` slab, so
    the PR-2 no-full-HBM-histogram invariant degrades gracefully to
    "no second full tensor".

    Returns (row-order SplitScores, row-order n_node, hist2
    [k, S, F, B, C] in paired-row order).
    """
    from ..kernels.gain_ratio.kernel import _round_up
    from ..kernels.split_scan.kernel import init_carry, split_scan_block

    k = weights.shape[0]
    N, F = x_binned.shape
    S, B, R = config.frontier, config.n_bins, config.max_splits_per_level
    C = base_channels.shape[-1]
    # Off-path slab width (sized for S rows) keeps split_scan_block's
    # geometry — and therefore its running-best carry arithmetic —
    # identical to the reuse=off trace.
    W = hist_feature_slab(N, F, S, B, C)
    Fp = _round_up(F, W)
    xb = jnp.pad(x_binned, ((0, 0), (0, Fp - F)))
    mask = (
        feature_mask if feature_mask is not None else jnp.ones((k, F), jnp.bool_)
    )
    mask = jnp.pad(mask, ((0, 0), (0, Fp - F)))
    cache_h = jnp.pad(cache["hist"], ((0, 0), (0, 0), (0, Fp - F)) + ((0, 0),) * 2)
    interpret = default_interpret()

    def slab(j, acc):
        carry, h2 = acc
        f0 = j * W
        xb_s = jax.lax.dynamic_slice_in_dim(xb, f0, W, axis=1)
        mask_s = jax.lax.dynamic_slice_in_dim(mask, f0, W, axis=1)
        ch_s = jax.lax.dynamic_slice_in_dim(cache_h, f0, W, axis=2)
        packed_s = _level_hists(xb_s, base_channels, weights, seg, config, n_slots=R)
        hist_s = sibling_expand(packed_s, ch_s, cache["perm"], cache["parent"], S)
        with scope("tns"):
            carry = split_scan_block(
                hist_s, mask_s, carry, f0,
                regression=config.regression, interpret=interpret,
            )
        h2 = jax.lax.dynamic_update_slice_in_dim(h2, hist_s, f0, axis=2)
        return carry, h2

    carry, h2 = jax.lax.fori_loop(
        0, Fp // W, slab,
        (init_carry(k, S, C), jnp.zeros((k, S, Fp, B, C), jnp.float32)),
    )
    scores = SplitScores(*carry)
    return scores, node_counts(scores, regression=config.regression), h2[:, :, :F]


def _permute_rows(perm: jnp.ndarray, a: jnp.ndarray) -> jnp.ndarray:
    """Gather the [k, S, ...] per-row descriptors ``a`` into slot order
    (``perm`` is ``sibling_perm``'s slot -> paired-row map)."""
    idx = perm.reshape(perm.shape + (1,) * (a.ndim - 2))
    return jnp.take_along_axis(a, idx, axis=1)


def reuse_expand_scores(
    packed_h, cache, feature_mask, config: ForestConfig,
):
    """Post-combine half of the reuse task group, shared with the
    streaming drivers (whose packed histogram accumulates over blocks
    before this runs once per level): expand the packed tensor against
    the cache (``parent - small``), score the paired rows, and permute
    the O(k*S) descriptors to slot order.

    Returns (slot-order SplitScores, n_node, hist2 paired-row tensor,
    perm) — the latter two are the next cache's ``hist`` / ``perm``.
    """
    S = config.frontier
    hist2 = sibling_expand(
        packed_h, cache["hist"], cache["perm"], cache["parent"], S
    )
    perm = sibling_perm(cache["small_right"], S)
    with scope("tns"):
        scores_r, n_r = level_scores(
            hist2, feature_mask, regression=config.regression,
            backend=resolve_split_backend(config.split_backend),
        )
    scores = jax.tree_util.tree_map(partial(_permute_rows, perm), scores_r)
    return scores, _permute_rows(perm, n_r), hist2, perm


@scope("task_group")
def reuse_level_task_group(
    x_binned, base_channels, weights, sample_slot, slot_node, cache,
    config: ForestConfig, plane: CollectivePlane,
):
    """Reuse-mode T_GR + T_NS task group.

    Histogram ONLY the samples routed to small children (R rank
    segments instead of S slot segments — ``sibling_segments`` parks
    everything else into the dump row, the same masking machinery
    early-exit uses for dead trees), combine the *packed* tensor on the
    plane (half the psum / psum_scatter bytes of the off path),
    reconstruct large children as ``parent - small`` post-combine so
    every shard agrees, and score the paired-row tensor. Only the
    O(k*S) split descriptors are permuted back to slot order —
    reordering the [k, S, F, B, C] tensor itself would be a full extra
    memory pass, which is why the cache stores paired rows plus their
    ``perm``.

    Returns (slot-order merged SplitScores, n_node, next cache dict
    missing its ``parent`` / ``small_right`` entries — ``level_step``
    fills those from ``sibling_plan`` once the level is planned).
    """
    S, R = config.frontier, config.max_splits_per_level
    tree_live = jnp.any(slot_node >= 0, axis=1)
    w_level = weights * tree_live[:, None].astype(weights.dtype)
    seg = sibling_segments(sample_slot, cache["small_right"])
    split_be = resolve_split_backend(config.split_backend)

    if plane.combine_hist is None and split_be == "pallas":
        perm = sibling_perm(cache["small_right"], S)
        scores_r, n_r, hist2 = fused_reuse_level_scores(
            x_binned, base_channels, w_level, seg, plane.level_mask,
            cache, config,
        )
        scores = jax.tree_util.tree_map(partial(_permute_rows, perm), scores_r)
        n_node = _permute_rows(perm, n_r)
    else:
        packed_h = _level_hists(
            x_binned, base_channels, w_level, seg, config, n_slots=R
        )
        if plane.combine_hist is not None:
            packed_h = plane.combine_hist(packed_h)   # half the wire bytes
        scores, n_node, hist2, perm = reuse_expand_scores(
            packed_h, cache, plane.level_mask, config
        )

    scores, n_node = plane.merge_winners(scores, n_node)
    return scores, n_node, {"hist": hist2, "perm": perm}


# ---------------------------------------------------------------------------
# The level-step pieces — shared by every plane and the streaming driver
# ---------------------------------------------------------------------------


def init_growth_state(
    base_channels: jnp.ndarray,   # [N, C] (local shard in distributed mode)
    weights: jnp.ndarray,         # [k, N]
    config: ForestConfig,
    plane: CollectivePlane,
    *,
    rng: Optional[jnp.ndarray] = None,
    n_features: Optional[int] = None,            # local-shard F; enables hist_reuse
) -> GrowthState:
    """Forest with the root node populated + an empty level-0 frontier.

    ``n_features`` opts the state into the ``hist_reuse`` cache (when
    the config and budget allow it): callers that do not thread it get
    the reuse-off pytree structure, so existing states and checkpoints
    are untouched."""
    k, S = config.n_trees, config.frontier
    forest = init_forest(config)
    root_counts = plane.reduce_root(
        jnp.einsum("kn,nc->kc", weights, base_channels)
    )
    forest = dataclasses.replace(
        forest, class_counts=forest.class_counts.at[:, 0].set(root_counts)
    )
    if config.regression:
        forest = dataclasses.replace(
            forest, value=forest.value.at[:, 0].set(_safe_mean(root_counts))
        )
    hist_cache = None
    if n_features is not None and resolve_hist_reuse(config, n_features):
        hist_cache = init_hist_cache(config, plane.hist_width(n_features))
    return GrowthState(
        forest=forest,
        slot_node=jnp.full((k, S), -1, jnp.int32).at[:, 0].set(0),
        sample_slot=jnp.zeros((k, weights.shape[1]), jnp.int32),
        rng=rng if rng is not None else jax.random.PRNGKey(0),
        level=jnp.asarray(0, jnp.int32),
        hist_cache=hist_cache,
    )


@scope("task_group")
def level_task_group(
    x_binned, base_channels, weights, sample_slot, slot_node,
    config: ForestConfig, plane: CollectivePlane,
):
    """One level's T_GR + T_NS task group: local scores through the
    plane's histogram combine, then the cross-shard winner merge.

    Trees whose frontiers already died (no live slot) get their DSI
    weights masked to zero, so finished trees contribute zero-weight
    work inside each ``tree_chunk`` task group — the engine analogue of
    the paper's schedulers not dispatching tasks for finished trees.
    """
    tree_live = jnp.any(slot_node >= 0, axis=1)               # [k]
    w_level = weights * tree_live[:, None].astype(weights.dtype)
    scores_loc, n_loc = chunked_level_scores(
        x_binned, base_channels, w_level, sample_slot,
        plane.level_mask, config, hist_reduce=plane.combine_hist,
    )
    return plane.merge_winners(scores_loc, n_loc)


@scope("plan_write")
def plan_level(
    scores: SplitScores, n_node: jnp.ndarray, slot_node: jnp.ndarray,
    config: ForestConfig, level: jnp.ndarray,
):
    """T_NS stage 2: admit splits (gain + support gates, beam rank) and
    fix this level's child-pool band. Returns (split_rank, is_split,
    child_base)."""
    n_max = config.max_splits_per_level
    active = slot_node >= 0
    valid = (
        active
        & (scores.gain_ratio > config.min_gain)
        & (n_node >= config.min_samples_split)
    )
    split_rank = _rank_splits(scores.gain_ratio, valid, n_max)    # [k, S]
    is_split = split_rank >= 0
    return split_rank, is_split, child_band(level, n_max)


def child_band(level, n_max: int):
    """First pool id of the children written at ``level``: level L's
    children fill ``[1 + 2*n_max*L, 1 + 2*n_max*(L+1))``."""
    return 1 + 2 * n_max * level


@scope("plan_write")
def write_level(
    forest: Forest, slot_node, split_rank, is_split, child_base,
    scores: SplitScores, config: ForestConfig,
) -> Forest:
    """Write this level's split descriptors + child nodes into the pool
    (non-split slots dump into the pad node, sanitized at the end)."""
    pad = config.max_nodes          # scatter dump index
    t_idx = jnp.arange(config.n_trees)[:, None]
    left_id = child_base + 2 * split_rank
    node_or_pad = jnp.where(is_split, slot_node, pad)

    feature = forest.feature.at[t_idx, node_or_pad].set(
        jnp.where(is_split, scores.feature, -1)
    )
    threshold = forest.threshold.at[t_idx, node_or_pad].set(scores.threshold)
    left_child = forest.left_child.at[t_idx, node_or_pad].set(left_id)

    lid = jnp.where(is_split, left_id, pad)
    rid = jnp.where(is_split, left_id + 1, pad)
    class_counts = forest.class_counts.at[t_idx, lid].set(scores.left_counts)
    class_counts = class_counts.at[t_idx, rid].set(scores.right_counts)
    if config.regression:
        lval = _safe_mean(scores.left_counts)
        rval = _safe_mean(scores.right_counts)
        value = forest.value.at[t_idx, lid].set(lval).at[t_idx, rid].set(rval)
    else:
        value = forest.value

    return dataclasses.replace(
        forest,
        feature=feature,
        threshold=threshold,
        left_child=left_child,
        class_counts=class_counts,
        value=value,
    )


@scope("route")
def route_level(
    x_binned, sample_slot, split_rank, scores: SplitScores,
    plane: CollectivePlane,
) -> jnp.ndarray:
    """Route samples to child slots (the paper's "distribute the
    data-index list of {v01, v02, ...} to the slaves")."""
    live = sample_slot >= 0
    s_safe = jnp.where(live, sample_slot, 0)
    rank_i = jnp.take_along_axis(split_rank, s_safe, 1)            # [k, N]
    f_i = jnp.take_along_axis(scores.feature, s_safe, 1)
    thr_i = jnp.take_along_axis(scores.threshold, s_safe, 1)
    go_right = plane.broadcast_route(x_binned, f_i, thr_i)
    return jnp.where(live & (rank_i >= 0), 2 * rank_i + go_right, -1)


def stream_block_step(
    hist_acc, xb_b, base_b, w_b, slot_b, slot_node,
    split_rank, scores: Optional[SplitScores],
    config: ForestConfig, plane: CollectivePlane, *, route: bool,
    small_right: Optional[jnp.ndarray] = None,
):
    """ONE device call per (block, level) of the streaming data plane.

    Fuses the route and histogram passes the PR-4 driver ran as two
    separate sweeps: route the block's samples from the *previous*
    level's frontier into this level's child slots (``route=True`` from
    level 1 on; ``split_rank``/``scores`` are that level's plan), then
    immediately fold the block into this level's histogram carry — so
    each level reads every block exactly once, and the per-sample slot
    table ``slot_b`` stays device-resident across levels (it is carried
    through this call, never round-tripped to the host).

    ``base_b`` (label channels) and ``w_b`` (DSI weights) are the
    per-block constants a ``BlockFeeder`` pins on device once for the
    whole growth. Works on any plane: ``route_level`` goes through
    ``plane.broadcast_route`` (identity gather locally, feature-axis
    psum on the mesh) and the histogram stays a local partial — the
    plane's ``combine_hist`` runs once per level in the plan step, not
    per block.

    With ``small_right`` (the sibling-subtraction reuse plane,
    ``config.hist_reuse``) the block is histogrammed into the *packed*
    ``max_splits_per_level`` rank segments — only samples routed to
    small children contribute; everything else parks in the dump row —
    so the accumulated carry (and, on the mesh, the per-level combine)
    is half the off-path tensor. ``hist_acc`` must then be the packed
    ``[k, R, F, B, C]`` carry.

    Returns ``(hist_acc + block_hist, routed slot_b)``.
    """
    if route:
        slot_b = route_level(xb_b, slot_b, split_rank, scores, plane)
    tree_live = jnp.any(slot_node >= 0, axis=1)
    w_lvl = w_b * tree_live[:, None].astype(w_b.dtype)
    if small_right is None:
        slots, n_slots = slot_b, config.frontier
    else:
        slots = sibling_segments(slot_b, small_right)
        n_slots = config.max_splits_per_level
    with scope("tgr"):
        h = level_histograms(
            xb_b, base_b, w_lvl, slots,
            n_slots=n_slots, n_bins=config.n_bins,
            packed=config.packed_hist and not config.regression,
            backend=config.hist_backend,
        )
    return hist_acc + h, slot_b


@scope("route")
def settle_leaves(node, sample_slot, level, config: ForestConfig) -> jnp.ndarray:
    """Pool node of every sample once ``level`` has been routed.

    A sample routed to child slot ``j`` sits in pool node
    ``child_band(level) + j`` (``next_frontier`` gives slot ``j`` that
    node); a sample parked at -1 stays in the node it was in, which is
    then a leaf. Carried over every level from the root (node 0), this
    is the leaf ``forest.route_to_leaves`` finds by walking the finished
    forest, without a gather."""
    base = child_band(level, config.max_splits_per_level)
    return jnp.where(sample_slot >= 0, base + sample_slot, node)


@scope("plan_write")
def next_frontier(is_split, child_base, n_slots: int) -> jnp.ndarray:
    """Next level's frontier: this level's children, densely packed."""
    j = jnp.arange(n_slots)[None, :]
    n_children = 2 * is_split.sum(-1, keepdims=True)
    return jnp.where(j < n_children, child_base + j, -1).astype(jnp.int32)


def finalize_forest(forest: Forest) -> Forest:
    """Sanitize the pad slot after growth.

    Every non-split frontier slot dumps its writes into the pad node,
    so its content is "whatever the last executed level wrote" — a
    function of how MANY levels ran. Resetting it to the leaf defaults
    makes forests bit-identical across {early-exit, fixed-depth} x
    {streamed, resident} x planes, and is semantically free: no real
    node ever points at the pad slot, and the fused traversal kernel
    (which reads every pool row) sees zero payload for it.
    """
    pad = forest.config.max_nodes
    return dataclasses.replace(
        forest,
        feature=forest.feature.at[:, pad].set(-1),
        threshold=forest.threshold.at[:, pad].set(0),
        left_child=forest.left_child.at[:, pad].set(-1),
        class_counts=forest.class_counts.at[:, pad].set(0.0),
        value=forest.value.at[:, pad].set(0.0),
    )


# ---------------------------------------------------------------------------
# The engine loop
# ---------------------------------------------------------------------------


def level_step(
    x_binned: jnp.ndarray,
    base_channels: jnp.ndarray,
    weights: jnp.ndarray,
    state: GrowthState,
    config: ForestConfig,
    plane: CollectivePlane,
) -> GrowthState:
    """ONE level of growth: task group -> plan -> write -> route ->
    frontier, threaded through the ``GrowthState`` carry.

    This is the body of ``grow``'s ``lax.while_loop`` AND the body of
    the host-driven ``grow_checkpointed`` loop — the same traced
    computation either way, so a run that checkpoints between levels
    produces the bit-identical forest of an uninterrupted ``grow``.

    With ``state.hist_cache`` present (``ForestConfig.hist_reuse``) the
    task group runs the sibling-subtraction path and the carry's cache
    is refreshed with this level's paired histograms plus the next
    level's small-side plan; the branch is on pytree *structure*, so
    both modes are one traced computation each.

    Each phase runs under a profiler scope (``core/tracing``):
    ``prf.task_group`` (``prf.tgr`` and ``prf.tns`` around the two
    kernels inside it), ``prf.plan_write`` and ``prf.route``.
    """
    if state.hist_cache is None:
        scores, n_node = level_task_group(
            x_binned, base_channels, weights, state.sample_slot,
            state.slot_node, config, plane,
        )
        new_cache = None
    else:
        scores, n_node, new_cache = reuse_level_task_group(
            x_binned, base_channels, weights, state.sample_slot,
            state.slot_node, state.hist_cache, config, plane,
        )
    split_rank, is_split, child_base = plan_level(
        scores, n_node, state.slot_node, config, state.level
    )
    forest = write_level(
        state.forest, state.slot_node, split_rank, is_split, child_base,
        scores, config,
    )
    sample_slot = route_level(
        x_binned, state.sample_slot, split_rank, scores, plane
    )
    slot_node = next_frontier(is_split, child_base, config.frontier)
    with scope("plan_write"):
        if new_cache is not None:
            parent, small_right = sibling_plan(
                scores, split_rank, is_split,
                n_ranks=config.max_splits_per_level,
                regression=config.regression,
            )
            new_cache = dict(new_cache, parent=parent, small_right=small_right)
        level = state.level + 1
    return GrowthState(
        forest=forest,
        slot_node=slot_node,
        sample_slot=sample_slot,
        rng=state.rng,
        level=level,
        hist_cache=new_cache,
    )


def grow_checkpointed(
    x_binned: jnp.ndarray,
    base_channels: jnp.ndarray,
    weights: jnp.ndarray,
    config: ForestConfig,
    plane: CollectivePlane,
    *,
    rng: Optional[jnp.ndarray] = None,
    manager=None,
    resume_from: Optional[str] = None,
    on_level=None,
) -> Forest:
    """``grow`` with per-level ``GrowthState`` checkpointing.

    A host-driven loop over the jitted ``level_step`` — each iteration
    runs the identical traced level-step of the ``lax.while_loop``
    engine, so the forest is bit-identical to ``grow`` on the same
    plane. Between levels the full carry (forest, frontier, per-sample
    slots, rng, level — everything a crash would lose) is handed to
    ``manager.maybe_save`` (atomic-rename checkpoints,
    ``checkpoint.CheckpointManager``); ``resume_from`` names a
    checkpoint directory whose newest *CRC-verified* step restores the
    carry (``checkpoint.restore_latest_valid`` — corrupt or torn steps
    are skipped, so a byte-flipped newest checkpoint costs one level of
    recompute, never a poisoned carry) and growth continues from the
    level after it. An empty/missing/fully-corrupt ``resume_from``
    directory falls back to a fresh start (the ``ElasticRunner``
    convention), so crash-retry supervisors need no
    has-a-checkpoint-yet branch.

    ``on_level(level, state)`` fires after each completed level (and
    after its checkpoint, so a raise here models a crash at the level
    boundary with the level's checkpoint already durable).
    """
    state = None
    if resume_from is not None:
        from ..checkpoint.checkpoint import restore_latest_valid

        like = init_growth_state(
            base_channels, weights, config, plane, rng=rng,
            n_features=x_binned.shape[1],
        )
        restored = restore_latest_valid(like, resume_from)
        if restored is not None:
            state, _ = restored
    if state is None:
        state = init_growth_state(
            base_channels, weights, config, plane, rng=rng,
            n_features=x_binned.shape[1],
        )

    step = jax.jit(
        lambda xb, base, w, st: level_step(xb, base, w, st, config, plane)
    )
    while int(state.level) < config.max_depth and bool(
        np.any(np.asarray(state.slot_node) >= 0)
    ):
        state = step(x_binned, base_channels, weights, state)
        if manager is not None:
            manager.maybe_save(state, int(state.level))
        if on_level is not None:
            on_level(int(state.level), state)
    return finalize_forest(state.forest)


def grow_with_leaves(
    x_binned: jnp.ndarray,        # [N, F] uint8 (local shard in distributed mode)
    base_channels: jnp.ndarray,   # [N, C]
    weights: jnp.ndarray,         # [k, N] DSI in-bag multiplicities
    config: ForestConfig,
    plane: CollectivePlane,
    *,
    rng: Optional[jnp.ndarray] = None,
) -> tuple[Forest, jnp.ndarray]:
    """Level-synchronous growth over ``plane`` — the unified engine.

    A ``lax.while_loop`` threads the full ``GrowthState`` carry through
    the level-step; with ``config.early_exit`` the loop also stops as
    soon as every tree's frontier is empty (the paper's schedulers
    dispatching no tasks for finished trees), which skips entire levels
    of histogram + routing work for shallow-converging forests.

    Routing moves every sample, in-bag or not, so the loop also carries
    each sample's pool node (``settle_leaves``). Returns the forest and
    the ``[k, N]`` int32 leaf of every sample under every tree: equal to
    ``forest.route_to_leaves(forest, x_binned)``, with no second walk.
    """
    depth = config.max_depth
    state = init_growth_state(
        base_channels, weights, config, plane, rng=rng,
        n_features=x_binned.shape[1],
    )

    def cond(carry):
        state, _ = carry
        more = state.level < depth
        if config.early_exit:
            more = more & jnp.any(state.slot_node >= 0)
        return more

    def body(carry):
        state, node = carry
        new = level_step(x_binned, base_channels, weights, state, config, plane)
        return new, settle_leaves(node, new.sample_slot, state.level, config)

    state, node = jax.lax.while_loop(
        cond, body, (state, jnp.zeros_like(state.sample_slot))
    )
    return finalize_forest(state.forest), node


def grow(
    x_binned: jnp.ndarray,
    base_channels: jnp.ndarray,
    weights: jnp.ndarray,
    config: ForestConfig,
    plane: CollectivePlane,
    *,
    rng: Optional[jnp.ndarray] = None,
) -> Forest:
    """``grow_with_leaves`` without the leaves (the mesh trainer's loop;
    the compiler drops the unused leaf carry)."""
    return grow_with_leaves(
        x_binned, base_channels, weights, config, plane, rng=rng
    )[0]
