"""Distributed PRF — vertical data-partitioning on a device mesh (paper §4).

Sharding layout (the paper's data-parallel optimization, §4.1):

  x_binned [N, F] : P(sample_axes, feature_axis)   <- vertical partitioning:
                    features pinned to `model` shards, samples to `data`
  y        [N]    : P(sample_axes)
  weights  [k, N] : P(None, sample_axes)           <- DSI counts, §4.1.2
  forest          : replicated (small)

Communication structure (== the paper's task DAG, §4.2):

  T_GR   per-device histograms over its (sample x feature) block, then one
         ``psum`` over the sample axes — the *only* large collective.
         Features never move; gain-ratio math is local to feature shards
         (paper: "tasks dispatched to the slaves where the subset is
         located", LocalScheduler).
  T_NS   each shard scores its own post-combine feature slice with the
         split backend selected by ``config.split_backend`` (the fused
         pallas split-scan kernel on TPU — histogram slabs consumed in
         VMEM, only per-(tree, slot) winners emerge), then winners are
         argmax-merged across shards: an ``all_gather`` of the [k, S]
         per-shard best gain ratios + masked ``psum``s of the tiny
         O(k*S) winner descriptors and the per-sample go-left/right bits
         (paper: ClusterScheduler synchronization point). Histogram
         slabs are never shipped to a central scorer.

Bootstrap is *stratified per sample-shard* (each shard draws N_local of
its own N_local rows): the Spark implementation samples globally; the
stratified variant has identical marginal statistics, lower variance, and
needs no cross-shard index exchange. Noted as an adaptation in DESIGN.md.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .dsi import bootstrap_counts
from .engine import (
    CollectivePlane, _gather_feature_bins, _safe_mean, finalize_forest, grow,
    init_forest, init_growth_state, init_hist_cache, level_step,
    next_frontier, plan_level, resolve_hist_reuse, reuse_expand_scores,
    stream_block_step, write_level,
)
from .types import GrowthState
from .gain import (
    SplitScores, level_scores, multiway_gain_ratio, resolve_split_backend,
    sibling_plan,
)
from .histograms import class_channels, level_histograms, regression_channels
from .types import Forest, ForestConfig


def _multi_axis_index(axes: Sequence[str]) -> jnp.ndarray:
    """Linearized index over possibly-multiple mesh axes (row-major)."""
    idx = jnp.int32(0)
    for a in axes:
        idx = idx * jax.lax.axis_size(a) + jax.lax.axis_index(a)
    return idx


def _masked_psum(val, mine, axis):
    """Select `val` from the shard where `mine` is True; result on all shards."""
    return jax.lax.psum(jnp.where(mine, val, jnp.zeros_like(val)), axis)


def _global_best_splits(
    scores: SplitScores, n_node, axes, f_global_local: jnp.ndarray,
    n_bins: int,
):
    """T_NS across shards: gather per-shard leaders, pick the winner.

    ``axes``: mesh axes the candidate splits are sharded over — just the
    feature axis in the paper-faithful layout, or (data, feature) when
    the histogram combine is a reduce-scatter (§Perf).
    ``f_global_local``: this shard's features mapped to global ids.

    Equal-gain ties are broken on the smallest global
    ``(feature, threshold)`` key — the order the single-host flat argmax
    uses — NOT on gather order: under the reduce-scatter layout the
    shards' feature ranges interleave over the data axis, so gather
    order disagrees with global feature order and tie-breaking on it
    made ``psum_scatter`` forests diverge from every other plane (the
    paper-faithful psum layout gathers shards in feature order, where
    the two rules coincide). This keeps all planes bit-identical.
    """
    axes = tuple(axes)
    my = _multi_axis_index(axes)
    gr_all = jax.lax.all_gather(scores.gain_ratio, axes)            # [P, k, S]
    best_gr = jnp.max(gr_all, axis=0)
    key = f_global_local * n_bins + scores.threshold                # [k, S]
    key_all = jax.lax.all_gather(key, axes)                         # [P, k, S]
    key_all = jnp.where(gr_all == best_gr, key_all, jnp.iinfo(jnp.int32).max)
    win = jnp.argmin(key_all, axis=0)                               # [k, S]
    mine = win == my
    f_global = _masked_psum(f_global_local, mine, axes)
    thr = _masked_psum(scores.threshold, mine, axes)
    lcnt = _masked_psum(scores.left_counts, mine[..., None], axes)
    rcnt = _masked_psum(scores.right_counts, mine[..., None], axes)
    n_node = _masked_psum(n_node, mine, axes)
    return SplitScores(best_gr, f_global, thr, lcnt, rcnt), n_node, mine


class MeshPlane(CollectivePlane):
    """The engine's collective plane for the vertical-partition mesh.

    T_GR combine strategy (``combine_hist``): plain psum (paper-faithful:
    every sample shard ends with the full feature-shard histogram) or
    reduce-scatter (§Perf: histogram shards over (sample x feature) —
    half the wire bytes, 1/P_data of the redundant gain-ratio compute).
    ``merge_winners`` is the T_NS cross-shard argmax merge
    (``_global_best_splits``), mapping per-shard feature ids to global
    ids first. ``broadcast_route``: the winning feature lives on exactly
    one feature shard; it computes the go-right bit, a masked psum
    broadcasts it (the paper's "result distributed to all slaves").
    """

    def __init__(
        self, config: ForestConfig, n_local_features: int, mask_loc,
        *, sample_axes, feature_axis,
    ):
        self.sample_axes = tuple(sample_axes)
        self.feature_axis = feature_axis
        self.n_bins = config.n_bins
        self.Fl = Fl = n_local_features
        self.midx = jax.lax.axis_index(feature_axis)
        self.use_rs = (
            config.hist_reduce == "psum_scatter"
            and len(self.sample_axes) == 1
            and Fl % jax.lax.axis_size(self.sample_axes[0]) == 0
        )
        if self.use_rs:
            self.didx = jax.lax.axis_index(self.sample_axes[0])
            self.fl_sub = Fl // jax.lax.axis_size(self.sample_axes[0])
            mask_src = (
                mask_loc if mask_loc is not None
                else jnp.ones((config.n_trees, Fl), jnp.bool_)
            )
            # Post-scatter each shard scores its (data, feature) slice.
            self.level_mask = jax.lax.dynamic_slice_in_dim(
                mask_src, self.didx * self.fl_sub, self.fl_sub, 1
            )
            self.combine_hist = lambda h: jax.lax.psum_scatter(
                h, self.sample_axes[0], scatter_dimension=2, tiled=True
            )
        else:
            self.level_mask = mask_loc
            self.combine_hist = lambda h: jax.lax.psum(h, self.sample_axes)

    def reduce_root(self, root_counts):
        return jax.lax.psum(root_counts, self.sample_axes)

    def merge_winners(self, scores, n_node):
        if self.use_rs:
            f_glob = scores.feature + self.midx * self.Fl + self.didx * self.fl_sub
            axes = (self.sample_axes[0], self.feature_axis)
        else:
            f_glob = scores.feature + self.midx * self.Fl
            axes = (self.feature_axis,)
        scores, n_node, _ = _global_best_splits(
            scores, n_node, axes, f_glob, self.n_bins
        )
        return scores, n_node

    def hist_width(self, n_features: int) -> int:
        # The hist_reuse cache stores POST-combine histograms: the full
        # local feature shard under psum, only the post-scatter slice
        # under reduce-scatter (the cache never widens the rs layout).
        return self.fl_sub if self.use_rs else n_features

    def broadcast_route(self, xb_loc, f_i, thr_i):
        f_shard = f_i // self.Fl                                 # global ids
        f_here = jnp.where(f_shard == self.midx, f_i - self.midx * self.Fl, 0)
        bins_i = _gather_feature_bins(xb_loc, f_here)            # [k, Nl]
        go_loc = jnp.where(
            f_shard == self.midx, (bins_i > thr_i).astype(jnp.int32), 0
        )
        return jax.lax.psum(go_loc, self.feature_axis)


def _grow_sharded(
    xb_loc, base_loc, w_loc, mask_loc, config: ForestConfig,
    *, sample_axes, feature_axis,
):
    """Level-synchronous growth on one device's (sample x feature) block
    — a thin entry point over the unified engine (core/engine.py)."""
    plane = MeshPlane(
        config, xb_loc.shape[1], mask_loc,
        sample_axes=sample_axes, feature_axis=feature_axis,
    )
    return grow(xb_loc, base_loc, w_loc, config, plane)


# ---------------------------------------------------------------------------
# Mesh x streaming: host sample blocks fed into the collective plane
# ---------------------------------------------------------------------------


def _pad_rows(a: np.ndarray, pad: int, fill=0):
    if pad == 0:
        return np.ascontiguousarray(a)
    width = [(0, pad)] + [(0, 0)] * (a.ndim - 1)
    return np.pad(a, width, constant_values=fill)


def grow_sharded_checkpointed(
    x_binned,
    y: np.ndarray,
    weights: np.ndarray,
    config: ForestConfig,
    mesh: Mesh,
    feature_mask: Optional[np.ndarray] = None,
    *,
    sample_axes: Sequence[str] = ("data",),
    feature_axis: str = "model",
    manager=None,
    resume_from: Optional[str] = None,
    on_level=None,
) -> Forest:
    """Resident mesh growth with per-level checkpointing / crash resume.

    The mesh analogue of ``engine.grow_checkpointed``: a host-driven
    loop over ONE jitted ``shard_map`` call wrapping the engine's
    ``level_step`` on ``MeshPlane`` — the identical traced level-step of
    ``_grow_sharded``'s ``lax.while_loop``, so the forest is
    bit-identical to the uninterrupted trainer. Between levels the full
    ``GrowthState`` carry is handed to ``manager.maybe_save``; on
    resume the carry is restored with its original mesh shardings (the
    per-sample slot table goes back to ``P(None, sample_axes)``, the
    rest replicated). Rows are padded to the data-axis size with
    zero-weight samples, invisible to histograms and root counts.
    """
    sample_axes = tuple(sample_axes)
    from .api import _channels

    x_np = np.asarray(x_binned)
    y_np = np.asarray(y)
    w_np = np.asarray(weights, np.float32)
    D = int(np.prod([mesh.shape[a] for a in sample_axes]))
    pad = (-x_np.shape[0]) % D
    k, F = config.n_trees, x_np.shape[1]

    x_sh = NamedSharding(mesh, P(sample_axes, feature_axis))
    row_sh = NamedSharding(mesh, P(sample_axes))
    kn_sh = NamedSharding(mesh, P(None, sample_axes))

    xb = jax.device_put(_pad_rows(x_np, pad), x_sh)
    base_dev = _channels(jax.device_put(_pad_rows(y_np, pad), row_sh), config)
    w_dev = jax.device_put(_pad_rows(w_np.T, pad).T, kn_sh)
    mask_np = (
        np.ones((k, F), bool) if feature_mask is None
        else np.asarray(feature_mask, bool)
    )
    mask_dev = jax.device_put(mask_np, NamedSharding(mesh, P(None, feature_axis)))

    def make_plane(mask_loc):
        return MeshPlane(
            config, mask_loc.shape[1], mask_loc,
            sample_axes=sample_axes, feature_axis=feature_axis,
        )

    # The hist_reuse cache joins the carry (and therefore every
    # checkpoint): resolved host-side from the LOCAL feature width so it
    # matches what init_growth_state builds inside the shard_map. Its
    # histogram is feature-sharded (post-psum each feature shard keeps
    # its own slice; under reduce-scatter the slice is further split
    # over the data axis); the small index tables are replicated.
    Fl = F // int(mesh.shape[feature_axis])
    use_rs = (
        config.hist_reduce == "psum_scatter"
        and len(sample_axes) == 1 and Fl % D == 0
    )
    reuse = resolve_hist_reuse(config, Fl)
    cache_specs = None
    if reuse:
        hist_axes = (feature_axis, sample_axes[0]) if use_rs else feature_axis
        cache_specs = {
            "hist": P(None, None, hist_axes),
            "perm": P(), "parent": P(), "small_right": P(),
        }

    def init_kernel(base_loc, w_loc, mask_loc):
        st = init_growth_state(
            base_loc, w_loc, config, make_plane(mask_loc),
            n_features=Fl if reuse else None,
        )
        return st.forest, st.slot_node, st.sample_slot, st.rng, st.level, \
            st.hist_cache

    state_specs = (P(), P(), P(None, sample_axes), P(), P(), cache_specs)
    init_fn = jax.jit(jax.shard_map(
        init_kernel, mesh=mesh,
        in_specs=(P(sample_axes), P(None, sample_axes), P(None, feature_axis)),
        out_specs=state_specs,
        check_vma=False,
    ))

    def step_kernel(xb_loc, base_loc, w_loc, mask_loc, forest, slot_node,
                    slot_loc, rng, level, cache):
        st = level_step(
            xb_loc, base_loc, w_loc,
            GrowthState(
                forest=forest, slot_node=slot_node, sample_slot=slot_loc,
                rng=rng, level=level, hist_cache=cache,
            ),
            config, make_plane(mask_loc),
        )
        return st.forest, st.slot_node, st.sample_slot, st.rng, st.level, \
            st.hist_cache

    step_fn = jax.jit(jax.shard_map(
        step_kernel, mesh=mesh,
        in_specs=(
            P(sample_axes, feature_axis), P(sample_axes),
            P(None, sample_axes), P(None, feature_axis),
        ) + state_specs,
        out_specs=state_specs,
        check_vma=False,
    ))

    state = init_fn(base_dev, w_dev, mask_dev)
    if resume_from is not None:
        from ..checkpoint.checkpoint import restore_latest_valid

        shardings = jax.tree_util.tree_map(lambda a: a.sharding, state)
        restored = restore_latest_valid(
            state, resume_from, shardings
        )
        if restored is not None:
            state, _ = restored
    forest, slot_node, slot_loc, rng, level, cache = state
    while (
        int(level) < config.max_depth
        and bool(np.any(np.asarray(slot_node) >= 0))
    ):
        forest, slot_node, slot_loc, rng, level, cache = step_fn(
            xb, base_dev, w_dev, mask_dev,
            forest, slot_node, slot_loc, rng, level, cache,
        )
        if manager is not None:
            manager.maybe_save(
                (forest, slot_node, slot_loc, rng, level, cache), int(level)
            )
        if on_level is not None:
            on_level(int(level), forest)
    return finalize_forest(forest)




def grow_forest_streamed_sharded(
    x_binned,
    y: np.ndarray,
    weights: np.ndarray,
    config: ForestConfig,
    mesh: Mesh,
    feature_mask: Optional[np.ndarray] = None,
    *,
    sample_axes: Sequence[str] = ("data",),
    feature_axis: str = "model",
    prefetch: int = 2,
    manager=None,
    resume_from: Optional[str] = None,
    on_level=None,
    feeder_opts: Optional[dict] = None,
    quarantined: Sequence[int] = (),
    runtime=None,
    block_sizes: Optional[Sequence[int]] = None,
) -> Forest:
    """Out-of-core growth on the **mesh** plane — the streaming data
    plane composed with ``MeshPlane``'s collectives, lifting the
    per-host memory cap on the distributed path too.

    Per (block, level), ONE jitted ``shard_map`` call runs
    ``engine.stream_block_step`` on every device: each shard routes its
    (sample x feature) slice of the block (the winning feature's
    go-right bit broadcast by ``MeshPlane.broadcast_route``'s masked
    psum) and folds it into its **local** histogram partial — the
    ``combine_hist`` collective (psum or psum_scatter, per
    ``config.hist_reduce``) runs once per level in the plan step, not
    once per block, so streaming adds zero extra collective traffic.
    The per-shard partials live in a ``[D, k, S, F, B, C]`` carry
    sharded ``P(sample_axes, ..., feature_axis)`` (each data shard owns
    its row), and the per-sample slot table stays device-resident
    sharded ``P(None, sample_axes)``.

    Blocks are padded host-side to a multiple of the data-axis size
    with parked samples (``slot = -1``, zero weight) — invisible to
    histograms, routing, and root counts — so any block split shards.
    The result is bit-identical to resident ``_grow_sharded`` growth
    and to the local planes (the engine parity matrix).

    **Checkpointing** mirrors ``grow_forest_streamed``: ``manager``
    saves the driver's full inter-level carry (forest, frontier, level
    plan, per-block slot tables) after each level; ``resume_from``
    restores the latest carry — slot tables back to their
    ``P(None, sample_axes)`` sharding — and the level loop continues
    where it stopped, bit-identically. ``feeder_opts`` forwards
    retry/backoff/fault-injection knobs to the ``BlockFeeder``;
    ``quarantined`` block indices are dropped from every sweep.

    **Multi-process plane.** With ``runtime`` (a
    ``launch.multiproc.MultiHostMesh``) the same driver runs across
    ``jax.distributed`` processes: ``x_binned`` is then the list of
    per-block **host-local padded row slices** (each process holds only
    its own rows — see ``MultiHostMesh.local_row_range``), and
    ``block_sizes`` gives the global unpadded block sizes the local
    slices came from. Every device array is constructed through the
    runtime's addressable-slice ``put`` — blocks via a shard-aware
    feeder placement, carries via ``zeros`` — so no host ever
    materializes a global row range, while the jitted kernels (and
    therefore the forest, bitwise) are identical to the single-process
    mesh. Checkpoints go through the multi-process manager/restore
    (process-0 manifest, per-host shard leaves).
    """
    from .api import _stream_setup

    sample_axes = tuple(sample_axes)
    if runtime is not None:
        if block_sizes is None:
            raise ValueError(
                "grow_forest_streamed_sharded(runtime=...) needs "
                "block_sizes — the global unpadded sizes the host-local "
                "block slices were cut from"
            )
        sizes = [int(n) for n in block_sizes]
        offsets = np.concatenate([[0], np.cumsum(sizes)])
        y_np = np.asarray(y)
        if config.regression:
            y_np = y_np.astype(np.float32)
        w_np = np.asarray(weights, dtype=np.float32)
        local_blocks = list(x_binned)
        F = local_blocks[0].shape[1]
    else:
        feeder0, y_np, w_np, sizes, offsets = _stream_setup(
            x_binned, y, weights, config, prefetch
        )
        F = feeder0.blocks[0].shape[1]
    D = int(np.prod([mesh.shape[a] for a in sample_axes]))
    k, S = config.n_trees, config.frontier
    B = config.n_bins
    C = 3 if config.regression else config.n_classes

    x_sh = NamedSharding(mesh, P(sample_axes, feature_axis))
    row_sh = NamedSharding(mesh, P(sample_axes))
    kn_sh = NamedSharding(mesh, P(None, sample_axes))
    rep_sh = NamedSharding(mesh, P())
    hist_spec = P(sample_axes, None, None, feature_axis)

    # Sibling-subtraction reuse (config.hist_reuse): per-block partials
    # scatter into R rank segments instead of S slots — the [D, k, R,
    # F, B, C] carry AND the per-level combine halve — and the plan
    # step reconstructs large children from the durable cache. The
    # cache histogram is feature-sharded exactly like the checkpointed
    # resident path's.
    Fl = F // int(mesh.shape[feature_axis])
    use_rs = (
        config.hist_reduce == "psum_scatter"
        and len(sample_axes) == 1 and Fl % D == 0
    )
    reuse = resolve_hist_reuse(config, Fl)
    n_rows = config.max_splits_per_level if reuse else S
    cache_sh = None
    if reuse:
        hist_axes = (feature_axis, sample_axes[0]) if use_rs else feature_axis
        cache_sh = {
            "hist": NamedSharding(mesh, P(None, None, hist_axes)),
            "perm": rep_sh, "parent": rep_sh, "small_right": rep_sh,
        }
        cache_specs = {
            "hist": P(None, None, hist_axes),
            "perm": P(), "parent": P(), "small_right": P(),
        }

    from ..data.pipeline import BlockFeeder

    pads = [(-n) % D for n in sizes]
    ms = [n + p for n, p in zip(sizes, pads)]       # padded global rows
    from .api import _channels

    base_dev, w_dev, slot_dev = [], [], []
    if runtime is not None:
        x_spec = P(sample_axes, feature_axis)
        feeder = BlockFeeder(
            local_blocks,
            placement=runtime.block_placement(ms, F, x_spec),
            prefetch=prefetch, quarantined=quarantined,
            **(feeder_opts or {}),
        )
        for i, m in enumerate(ms):
            o0 = offsets[i]
            lo, hi = runtime.local_row_range(m)
            nreal = max(min(hi, sizes[i]) - lo, 0)   # local non-pad rows
            yb = np.zeros((hi - lo,), y_np.dtype)
            yb[:nreal] = y_np[o0 + lo:o0 + lo + nreal]
            # Channels on the local rows only — _channels is row-wise,
            # so this is the row slice of the single-process build.
            ch = np.asarray(_channels(jnp.asarray(yb), config))
            base_dev.append(runtime.put(
                ch, (m,) + ch.shape[1:], P(sample_axes),
                box=[(lo, hi)] + [(0, s) for s in ch.shape[1:]],
            ))
            wb = np.zeros((k, hi - lo), np.float32)
            wb[:, :nreal] = w_np[:, o0 + lo:o0 + lo + nreal]
            w_dev.append(runtime.put(
                wb, (k, m), P(None, sample_axes), box=[(0, k), (lo, hi)],
            ))
            slot0 = np.zeros((k, hi - lo), np.int32)
            slot0[:, max(sizes[i] - lo, 0):] = -1    # pad rows stay parked
            slot_dev.append(runtime.put(
                slot0, (k, m), P(None, sample_axes), box=[(0, k), (lo, hi)],
            ))
    else:
        feeder = BlockFeeder(
            [_pad_rows(b, p) for b, p in zip(feeder0.blocks, pads)],
            placement=x_sh, prefetch=prefetch, quarantined=quarantined,
            **(feeder_opts or {}),
        )
        for i, p in enumerate(pads):
            o0, o1 = offsets[i], offsets[i + 1]
            # Channels built on device by the same _channels every other
            # plane uses; pad rows are zero-weight + parked, so their
            # channel content is irrelevant.
            base_dev.append(_channels(
                jax.device_put(_pad_rows(y_np[o0:o1], p), row_sh), config,
            ))
            w_dev.append(
                jax.device_put(_pad_rows(w_np[:, o0:o1].T, p).T, kn_sh)
            )
            slot0 = np.zeros((k, sizes[i] + p), np.int32)
            slot0[:, sizes[i]:] = -1                # pad rows stay parked
            slot_dev.append(jax.device_put(slot0, kn_sh))

    mask_np = (
        np.ones((k, F), bool) if feature_mask is None
        else np.asarray(feature_mask, bool)
    )
    mask_dev = (
        runtime.put_full(mask_np, P(None, feature_axis))
        if runtime is not None
        else jax.device_put(mask_np, NamedSharding(mesh, P(None, feature_axis)))
    )

    def make_plane(Fl, mask_loc=None):
        return MeshPlane(
            config, Fl, mask_loc,
            sample_axes=sample_axes, feature_axis=feature_axis,
        )

    def step_kernel_route(hist_part, xb_loc, base_loc, w_loc, slot_loc,
                          slot_node, split_rank, scores, small_right=None):
        h, slot_loc = stream_block_step(
            hist_part[0], xb_loc, base_loc, w_loc, slot_loc, slot_node,
            split_rank, scores, config, make_plane(xb_loc.shape[1]),
            route=True, small_right=small_right,
        )
        return h[None], slot_loc

    def step_kernel_first(hist_part, xb_loc, base_loc, w_loc, slot_loc,
                          slot_node, small_right=None):
        h, slot_loc = stream_block_step(
            hist_part[0], xb_loc, base_loc, w_loc, slot_loc, slot_node,
            None, None, config, make_plane(xb_loc.shape[1]), route=False,
            small_right=small_right,
        )
        return h[None], slot_loc

    data_specs = (hist_spec, P(sample_axes, feature_axis), P(sample_axes),
                  P(None, sample_axes), P(None, sample_axes), P())
    sr_specs = (P(),) if reuse else ()
    step_route = jax.jit(jax.shard_map(
        step_kernel_route, mesh=mesh,
        in_specs=data_specs + (P(), P()) + sr_specs,
        out_specs=(hist_spec, P(None, sample_axes)),
        check_vma=False,
    ))
    step_first = jax.jit(jax.shard_map(
        step_kernel_first, mesh=mesh,
        in_specs=data_specs + sr_specs,
        out_specs=(hist_spec, P(None, sample_axes)),
        check_vma=False,
    ))

    split_be = resolve_split_backend(config.split_backend)

    def _root_init(forest, hist_c):
        # Root counts: any feature's bin marginal of the level-0
        # histogram (slot/rank row 0) sums to the [k, C] root class
        # counts (identical on every shard — exact integer sums).
        root = hist_c[:, 0, 0].sum(axis=1)
        forest = dataclasses.replace(
            forest, class_counts=forest.class_counts.at[:, 0].set(root),
        )
        if config.regression:
            forest = dataclasses.replace(
                forest, value=forest.value.at[:, 0].set(_safe_mean(root)),
            )
        return forest

    def make_plan(init: bool):
        def plan_kernel(hist_part, forest, slot_node, level, mask_loc):
            plane = make_plane(hist_part.shape[3], mask_loc)
            hist_c = plane.combine_hist(hist_part[0])
            if init:
                forest = _root_init(forest, hist_c)
            scores_loc, n_loc = level_scores(
                hist_c, plane.level_mask, regression=config.regression,
                backend=split_be,
            )
            scores, n_node = plane.merge_winners(scores_loc, n_loc)
            split_rank, is_split, child_base = plan_level(
                scores, n_node, slot_node, config, level
            )
            forest = write_level(
                forest, slot_node, split_rank, is_split, child_base, scores,
                config,
            )
            return (
                forest, scores, split_rank,
                next_frontier(is_split, child_base, config.frontier),
            )

        def plan_kernel_reuse(hist_part, forest, slot_node, level, mask_loc,
                              cache):
            plane = make_plane(hist_part.shape[3], mask_loc)
            hist_c = plane.combine_hist(hist_part[0])   # packed: half the wire
            if init:
                forest = _root_init(forest, hist_c)
            scores, n_node, hist2, perm = reuse_expand_scores(
                hist_c, cache, plane.level_mask, config
            )
            scores, n_node = plane.merge_winners(scores, n_node)
            split_rank, is_split, child_base = plan_level(
                scores, n_node, slot_node, config, level
            )
            forest = write_level(
                forest, slot_node, split_rank, is_split, child_base, scores,
                config,
            )
            parent, small_right = sibling_plan(
                scores, split_rank, is_split,
                n_ranks=config.max_splits_per_level,
                regression=config.regression,
            )
            return (
                forest, scores, split_rank,
                next_frontier(is_split, child_base, config.frontier),
                {"hist": hist2, "perm": perm,
                 "parent": parent, "small_right": small_right},
            )

        if reuse:
            return jax.jit(jax.shard_map(
                plan_kernel_reuse, mesh=mesh,
                in_specs=(hist_spec, P(), P(), P(), P(None, feature_axis),
                          cache_specs),
                out_specs=(P(), P(), P(), P(), cache_specs),
                check_vma=False,
            ))
        return jax.jit(jax.shard_map(
            plan_kernel, mesh=mesh,
            in_specs=(hist_spec, P(), P(), P(), P(None, feature_axis)),
            out_specs=(P(), P(), P(), P()),
            check_vma=False,
        ))

    plan_init, plan_next = make_plan(True), make_plan(False)

    hist0 = (
        runtime.zeros((D, k, n_rows, F, B, C), hist_spec, jnp.float32)
        if runtime is not None
        else jax.device_put(
            jnp.zeros((D, k, n_rows, F, B, C), jnp.float32),
            NamedSharding(mesh, hist_spec),
        )
    )

    state = None
    if resume_from is not None:
        from ..checkpoint.checkpoint import restore_latest_valid
        from .api import _stream_state_like

        # The like-template is GLOBAL-shaped: cache width F (the mesh
        # shards its feature dim per cache_sh on restore).
        like = _stream_state_like(
            [n + p for n, p in zip(sizes, pads)], config,
            F if reuse else 0,
        )
        shardings = jax.tree_util.tree_map(lambda _: rep_sh, like)
        shardings["slots"] = [kn_sh for _ in like["slots"]]
        if reuse:
            shardings["hist_cache"] = cache_sh
        if runtime is not None:
            from ..launch.multiproc import restore_latest_valid_multiproc

            restored = restore_latest_valid_multiproc(
                like, resume_from, shardings, runtime
            )
        else:
            restored = restore_latest_valid(like, resume_from, shardings)
        if restored is not None:
            state, _ = restored
    if state is not None:
        forest, slot_node = state["forest"], state["slot_node"]
        scores, split_rank = state["scores"], state["split_rank"]
        slot_dev, start = list(state["slots"]), int(np.asarray(state["level"]))
        cache = state.get("hist_cache") if reuse else None
    else:
        slot0_np = np.full((k, S), -1, np.int32)
        slot0_np[:, 0] = 0
        slot_node = (
            runtime.put_full(slot0_np, P()) if runtime is not None
            else jax.device_put(jnp.asarray(slot0_np), rep_sh)
        )
        forest, scores, split_rank = None, None, None
        start = 0
        # Global cache width F — sharded per cache_sh (dim 2).
        if reuse:
            cache0 = init_hist_cache(config, F)
            cache = (
                {n: runtime.put_full(np.asarray(v), cache_specs[n])
                 for n, v in cache0.items()}
                if runtime is not None
                else jax.device_put(cache0, cache_sh)
            )
        else:
            cache = None

    def level_sweep(route: bool):
        hist = hist0
        sr = ((cache["small_right"],) if reuse else ())
        for i, xb_b in zip(feeder.live_blocks, feeder.sweep()):
            if route:
                hist, slot_dev[i] = step_route(
                    hist, xb_b, base_dev[i], w_dev[i], slot_dev[i],
                    slot_node, split_rank, scores, *sr,
                )
            else:
                hist, slot_dev[i] = step_first(
                    hist, xb_b, base_dev[i], w_dev[i], slot_dev[i], slot_node,
                    *sr,
                )
        return hist

    try:
        for level in range(start, config.max_depth):
            if not np.any(np.asarray(slot_node) >= 0):
                break
            hist = level_sweep(route=level > 0)
            plan = plan_next if forest is not None else plan_init
            if forest is None:
                f0 = init_forest(config)
                forest = (
                    jax.tree_util.tree_map(
                        lambda a: runtime.put_full(np.asarray(a), P()), f0
                    )
                    if runtime is not None else jax.device_put(f0, rep_sh)
                )
            if reuse:
                forest, scores, split_rank, slot_node, cache = plan(
                    hist, forest, slot_node, np.int32(level),
                    mask_dev, cache,
                )
            else:
                forest, scores, split_rank, slot_node = plan(
                    hist, forest, slot_node, np.int32(level),
                    mask_dev,
                )
            if manager is not None:
                manager.maybe_save({
                    "forest": forest, "slot_node": slot_node,
                    "scores": scores, "split_rank": split_rank,
                    "slots": slot_dev, "hist_cache": cache,
                    "level": np.int32(level + 1),
                }, level + 1)
            if on_level is not None:
                on_level(level + 1, forest)

        if forest is None:          # max_depth == 0: root node only
            def root_kernel(hist_part):
                plane = make_plane(hist_part.shape[3])
                hist_c = plane.combine_hist(hist_part[0])
                return hist_c[:, 0, 0].sum(axis=1)

            root_fn = jax.jit(jax.shard_map(
                root_kernel, mesh=mesh, in_specs=(hist_spec,), out_specs=P(),
                check_vma=False,
            ))
            # Host round-trip: the replicated root counts fetch cleanly
            # on every process, and the .at[].set below then runs on
            # purely local arrays (eager ops on multi-process global
            # arrays would raise).
            root = jnp.asarray(np.asarray(jax.device_get(
                root_fn(level_sweep(route=False))
            )))
            forest = init_forest(config)
            forest = dataclasses.replace(
                forest, class_counts=forest.class_counts.at[:, 0].set(root)
            )
            if config.regression:
                forest = dataclasses.replace(
                    forest, value=forest.value.at[:, 0].set(_safe_mean(root))
                )
    finally:
        feeder.close()
    if runtime is not None:
        # Forest leaves are fully replicated — pull them host-side so
        # finalize_forest (eager jnp) runs on local arrays.
        forest = jax.tree_util.tree_map(
            lambda a: jnp.asarray(np.asarray(jax.device_get(a))), forest
        )
    return finalize_forest(forest)


def oob_accuracy_streamed_sharded(
    forest: Forest,
    x_binned,
    y: np.ndarray,
    weights: np.ndarray,
    mesh: Mesh,
    *,
    sample_block: int = 0,
    sample_axes: Sequence[str] = ("data",),
    feature_axis: str = "model",
    prefetch: int = 2,
    feeder_opts: Optional[dict] = None,
    quarantined: Sequence[int] = (),
    runtime=None,
    block_sizes: Optional[Sequence[int]] = None,
    invalid_masks: Optional[dict] = None,
) -> jnp.ndarray:
    """Eq. (8) over host sample blocks on the mesh — per block, each
    shard routes its slice and psums its [k] correct/OOB partial counts;
    the counts accumulate across blocks (exact f32 integers, so the
    result is bit-identical to resident ``_oob_weights_sharded`` /
    single-host ``oob_accuracy``). Padded rows are masked via an
    explicit validity channel (their zero weight would otherwise read
    as OOB).

    With ``runtime`` (``launch.multiproc.MultiHostMesh``) ``x_binned``
    is each process's local row window of every padded block and
    ``block_sizes`` the global unpadded sizes; labels/weights/validity
    are placed as addressable slices and the replicated count outputs
    accumulate host-side. ``invalid_masks[i]`` (a local bool mask over
    block *i*'s window) zeroes extra rows out of the validity channel —
    exact-integer sums make that bitwise identical to dropping those
    rows, which is how the single-host path excludes imputed-label
    samples."""
    from ..data.pipeline import BlockFeeder, stream_blocks

    sample_axes = tuple(sample_axes)
    y_np = np.asarray(y)
    w_np = np.asarray(weights, dtype=np.float32)
    D = int(np.prod([mesh.shape[a] for a in sample_axes]))
    if runtime is not None:
        if block_sizes is None:
            raise ValueError(
                "oob_accuracy_streamed_sharded(runtime=...) needs "
                "block_sizes — the global unpadded sizes the host-local "
                "block slices were cut from"
            )
        blocks = list(x_binned)
        sizes = [int(n) for n in block_sizes]
    else:
        blocks = stream_blocks(
            x_binned, sample_block, what="oob_accuracy_streamed_sharded",
            n_y=y_np.shape[0], n_w=w_np.shape[1],
        )
        sizes = [b.shape[0] for b in blocks]
    offsets = np.concatenate([[0], np.cumsum(sizes)])
    pads = [(-n) % D for n in sizes]
    ms = [n + p for n, p in zip(sizes, pads)]

    x_sh = NamedSharding(mesh, P(sample_axes, feature_axis))
    row_sh = NamedSharding(mesh, P(sample_axes))
    kn_sh = NamedSharding(mesh, P(None, sample_axes))
    if runtime is not None:
        F = blocks[0].shape[1]
        feeder = BlockFeeder(
            blocks,
            placement=runtime.block_placement(
                ms, F, P(sample_axes, feature_axis)
            ),
            prefetch=prefetch, quarantined=quarantined,
            **(feeder_opts or {}),
        )
    else:
        feeder = BlockFeeder(
            [_pad_rows(np.asarray(b), p) for b, p in zip(blocks, pads)],
            placement=x_sh, prefetch=prefetch, quarantined=quarantined,
            **(feeder_opts or {}),
        )

    def kernel(xb_loc, y_loc, w_loc, valid_loc):
        leaves = _route_sharded(forest, xb_loc, feature_axis=feature_axis)
        counts = jnp.take_along_axis(
            forest.class_counts, leaves[..., None], axis=1
        )
        pred = jnp.argmax(counts, axis=-1)                       # [k, Nl]
        oob = (w_loc == 0.0).astype(jnp.float32) * valid_loc[None]
        correct = jax.lax.psum(
            jnp.sum(oob * (pred == y_loc[None]).astype(jnp.float32), 1),
            sample_axes,
        )
        total = jax.lax.psum(jnp.sum(oob, 1), sample_axes)
        return correct, total

    fn = jax.jit(jax.shard_map(
        kernel, mesh=mesh,
        in_specs=(P(sample_axes, feature_axis), P(sample_axes),
                  P(None, sample_axes), P(sample_axes)),
        out_specs=(P(), P()),
        check_vma=False,
    ))

    k = w_np.shape[0]
    try:
        if runtime is not None:
            # Replicated count outputs fetch cleanly on every process;
            # accumulating them host-side in f32 keeps the exact-integer
            # sums bitwise identical to the on-device accumulation.
            correct = np.zeros((k,), np.float32)
            total = np.zeros((k,), np.float32)
            for i, xb_b in zip(feeder.live_blocks, feeder.sweep()):
                o0, m = offsets[i], ms[i]
                lo, hi = runtime.local_row_range(m)
                nreal = max(min(hi, sizes[i]) - lo, 0)
                yb = np.zeros((hi - lo,), y_np.dtype)
                yb[:nreal] = y_np[o0 + lo:o0 + lo + nreal]
                wb = np.zeros((k, hi - lo), np.float32)
                wb[:, :nreal] = w_np[:, o0 + lo:o0 + lo + nreal]
                valid = np.zeros(hi - lo, np.float32)
                valid[:nreal] = 1.0
                if invalid_masks and i in invalid_masks:
                    valid[np.asarray(invalid_masks[i], bool)] = 0.0
                c, t = fn(
                    xb_b,
                    runtime.put(yb, (m,), P(sample_axes), box=[(lo, hi)]),
                    runtime.put(wb, (k, m), P(None, sample_axes),
                                box=[(0, k), (lo, hi)]),
                    runtime.put(valid, (m,), P(sample_axes),
                                box=[(lo, hi)]),
                )
                correct = correct + np.asarray(jax.device_get(c))
                total = total + np.asarray(jax.device_get(t))
            return jnp.asarray(np.where(
                total > 0, correct / np.maximum(total, np.float32(1.0)),
                np.float32(0.5),
            ).astype(np.float32))

        correct = jnp.zeros((k,), jnp.float32)
        total = jnp.zeros((k,), jnp.float32)
        for i, xb_b in zip(feeder.live_blocks, feeder.sweep()):
            o0, o1 = offsets[i], offsets[i + 1]
            valid = np.zeros(sizes[i] + pads[i], np.float32)
            valid[:sizes[i]] = 1.0
            c, t = fn(
                xb_b,
                jax.device_put(_pad_rows(y_np[o0:o1], pads[i]), row_sh),
                jax.device_put(_pad_rows(w_np[:, o0:o1].T, pads[i]).T, kn_sh),
                jax.device_put(valid, row_sh),
            )
            correct, total = correct + c, total + t
    finally:
        feeder.close()
    return jnp.where(total > 0, correct / jnp.maximum(total, 1.0), 0.5)


def predict_streamed_sharded(
    forest: Forest,
    x_binned,
    mesh: Mesh,
    *,
    sample_block: int = 0,
    sample_axes: Sequence[str] = ("data",),
    feature_axis: str = "model",
    prefetch: int = 2,
    feeder_opts: Optional[dict] = None,
) -> np.ndarray:
    """Distributed Eq. (10) prediction over host sample blocks — labels
    are per-sample, so the blocked sweep is bit-identical to
    ``predict_sharded`` on the full matrix; only one padded block is
    device-resident at a time. Returns [N] labels (host array)."""
    from ..data.pipeline import BlockFeeder, stream_blocks

    sample_axes = tuple(sample_axes)
    blocks = stream_blocks(
        x_binned, sample_block, what="predict_streamed_sharded"
    )
    sizes = [b.shape[0] for b in blocks]
    D = int(np.prod([mesh.shape[a] for a in sample_axes]))
    pads = [(-n) % D for n in sizes]
    x_sh = NamedSharding(mesh, P(sample_axes, feature_axis))
    feeder = BlockFeeder(
        [_pad_rows(np.asarray(b), p) for b, p in zip(blocks, pads)],
        placement=x_sh, prefetch=prefetch, **(feeder_opts or {}),
    )
    fn = jax.jit(jax.shard_map(
        partial(_vote_labels_kernel, forest, feature_axis=feature_axis),
        mesh=mesh,
        in_specs=(P(sample_axes, feature_axis),),
        out_specs=P(sample_axes),
        check_vma=False,
    ))
    try:
        out = [
            np.asarray(fn(xb_b))[:sizes[i]]
            for i, xb_b in enumerate(feeder.sweep())
        ]
    finally:
        feeder.close()
    return np.concatenate(out)


def _route_sharded(forest: Forest, xb_loc, *, feature_axis: str):
    """route_to_leaves when features are sharded over `feature_axis`."""
    k = forest.feature.shape[0]
    Nl, Fl = xb_loc.shape
    depth = forest.config.max_depth
    midx = jax.lax.axis_index(feature_axis)
    xb = xb_loc.astype(jnp.int32)

    def step(node, _):
        f = jnp.take_along_axis(forest.feature, node, 1)             # [k, Nl]
        leaf = f < 0
        f_shard = jnp.where(leaf, -1, f // Fl)
        f_here = jnp.where(f_shard == midx, f - midx * Fl, 0)
        b = _gather_feature_bins(xb, f_here)
        thr = jnp.take_along_axis(forest.threshold, node, 1)
        go_loc = jnp.where(f_shard == midx, (b > thr).astype(jnp.int32), 0)
        go = jax.lax.psum(go_loc, feature_axis)
        lc = jnp.take_along_axis(forest.left_child, node, 1)
        return jnp.where(leaf, node, lc + go), None

    node0 = jnp.zeros((k, Nl), jnp.int32)
    leaves, _ = jax.lax.scan(step, node0, None, length=depth)
    return leaves


def _dimred_sharded(xb_loc, base_loc, w_loc, config, key, *, sample_axes, feature_axis):
    """Distributed Alg. 3.1: local GR + global VI ranking."""
    k, Nl = w_loc.shape
    Fl = xb_loc.shape[1]
    slot0 = jnp.zeros((k, Nl), jnp.int32)
    hist = level_histograms(
        xb_loc, base_loc, w_loc, slot0, n_slots=1, n_bins=config.n_bins,
        backend=config.hist_backend,
    )
    hist = jax.lax.psum(hist, sample_axes)
    gr_loc = multiway_gain_ratio(hist[:, 0])                         # [k, Fl]
    gr = jax.lax.all_gather(gr_loc, feature_axis, axis=1, tiled=True)  # [k, F]
    from .dimred import select_features

    cfg = config.resolved(gr.shape[1])
    mask = select_features(
        gr, key, n_selected=cfg.n_selected, n_important=cfg.n_important
    )
    midx = jax.lax.axis_index(feature_axis)
    return jax.lax.dynamic_slice_in_dim(mask, midx * Fl, Fl, axis=1)


def _oob_weights_sharded(forest, xb_loc, y_loc, w_loc, *, sample_axes, feature_axis):
    """Eq. (8) with samples and features sharded."""
    leaves = _route_sharded(forest, xb_loc, feature_axis=feature_axis)
    counts = jnp.take_along_axis(forest.class_counts, leaves[..., None], axis=1)
    pred = jnp.argmax(counts, axis=-1)                               # [k, Nl]
    oob = (w_loc == 0.0).astype(jnp.float32)
    correct = jax.lax.psum(
        jnp.sum(oob * (pred == y_loc[None]).astype(jnp.float32), 1), sample_axes
    )
    total = jax.lax.psum(jnp.sum(oob, 1), sample_axes)
    return jnp.where(total > 0, correct / jnp.maximum(total, 1.0), 0.5)


def make_prf_train_fn(
    config: ForestConfig,
    mesh: Mesh,
    *,
    sample_axes: Sequence[str] = ("data",),
    feature_axis: str = "model",
):
    """Build the jit'd distributed PRF trainer for `mesh`.

    Returns (train_fn, in_shardings): ``train_fn(x_binned, y, seed_key)``
    -> Forest (replicated). This is the function the multi-pod dry-run
    lowers and compiles.
    """
    sample_axes = tuple(sample_axes)
    x_spec = P(sample_axes, feature_axis)
    y_spec = P(sample_axes)

    def train(x_binned, y, key):
        def kernel(xb_loc, y_loc, key):
            k_boot, k_dim = jax.random.split(
                jax.random.fold_in(key, _multi_axis_index(sample_axes))
            )
            Nl = xb_loc.shape[0]
            base_loc = (
                regression_channels(y_loc)
                if config.regression
                else class_channels(y_loc, config.n_classes)
            )
            # Stratified DSI bootstrap (see module docstring).
            w_loc = bootstrap_counts(k_boot, config.n_trees, Nl)

            mask_loc = None
            if config.feature_mode == "importance" and not config.regression:
                # identical key across shards => identical global mask
                k_dim_g = jax.random.fold_in(key, 7)
                mask_loc = _dimred_sharded(
                    xb_loc, base_loc, w_loc, config, k_dim_g,
                    sample_axes=sample_axes, feature_axis=feature_axis,
                )
            elif config.feature_mode == "random":
                from .dimred import random_feature_mask

                cfg = config.resolved(x_binned.shape[1])
                mask = random_feature_mask(
                    jax.random.fold_in(key, 7),
                    n_trees=config.n_trees,
                    n_features=x_binned.shape[1],
                    n_selected=cfg.n_selected,
                )
                midx = jax.lax.axis_index(feature_axis)
                Fl = xb_loc.shape[1]
                mask_loc = jax.lax.dynamic_slice_in_dim(mask, midx * Fl, Fl, 1)

            forest = _grow_sharded(
                xb_loc, base_loc, w_loc, mask_loc, config,
                sample_axes=sample_axes, feature_axis=feature_axis,
            )
            if config.weighted_voting and not config.regression:
                w = _oob_weights_sharded(
                    forest, xb_loc, y_loc, w_loc,
                    sample_axes=sample_axes, feature_axis=feature_axis,
                )
                forest = dataclasses.replace(forest, tree_weight=w)
            return forest

        return jax.shard_map(
            kernel,
            mesh=mesh,
            in_specs=(x_spec, y_spec, P()),
            out_specs=P(),
            check_vma=False,
        )(x_binned, y, key)

    in_shardings = (
        NamedSharding(mesh, x_spec),
        NamedSharding(mesh, y_spec),
        NamedSharding(mesh, P()),
    )
    return jax.jit(train, in_shardings=in_shardings), in_shardings


def _vote_labels_kernel(forest: Forest, xb_loc, *, feature_axis: str):
    """Per-device Eq. (10) voting over a feature-sharded block — the ONE
    kernel behind both the resident ``predict_sharded`` and the
    mesh-streamed ``predict_streamed_sharded`` sweeps."""
    leaves = _route_sharded(forest, xb_loc, feature_axis=feature_axis)
    counts = jnp.take_along_axis(forest.class_counts, leaves[..., None], axis=1)
    probs = counts / jnp.maximum(counts.sum(-1, keepdims=True), 1e-38)
    w = (
        forest.tree_weight
        if forest.config.weighted_voting
        else jnp.ones_like(forest.tree_weight)
    )
    from .voting import weighted_vote

    scores = weighted_vote(probs, w, soft=forest.config.soft_voting)
    return jnp.argmax(scores, -1)


def predict_sharded(forest: Forest, x_binned, mesh, *,
                    sample_axes=("data",), feature_axis="model"):
    """Distributed weighted-voting prediction (Eq. 10). Returns [N] labels."""
    sample_axes = tuple(sample_axes)
    fn = jax.shard_map(
        partial(_vote_labels_kernel, forest, feature_axis=feature_axis),
        mesh=mesh,
        in_specs=(P(sample_axes, feature_axis),),
        out_specs=P(sample_axes),
        check_vma=False,
    )
    return jax.jit(fn)(x_binned)


# ---------------------------------------------------------------------------
# Distributed bin-edge fitting (blocked quantile sketch over the mesh)
# ---------------------------------------------------------------------------


def fit_bins_sharded(
    x,
    n_bins: int,
    mesh: Mesh,
    *,
    sample_block: int,
    sample_axes: Sequence[str] = ("data",),
    max_size: Optional[int] = None,
    exclude_masks=None,
    runtime=None,
) -> np.ndarray:
    """Distributed bin-edge fitting: one quantile sketch per data shard,
    exchanged through the collective plane, merged host-side.

    The block list (``sample_blocks`` views of the source — typically an
    ``np.memmap``) is partitioned contiguously over the ``sample_axes``
    shards; each shard folds only its own blocks into a
    ``StreamingQuantileSketch``, so per-shard memory stays O(block) +
    O(F * max_size) — in a multi-process mesh each host would feed its
    local shard of the file. The per-feature summaries then cross the
    mesh as raw float64 **bit patterns** (uint32 words) through one
    ``all_gather`` over ``sample_axes`` — exact regardless of jax's x64
    mode — and are merged in shard order on the host. The result is
    deterministic, and while every summary is uncompressed it is bitwise
    identical to single-host ``fit_bins_blocked`` over the same blocks
    (and therefore to the resident ``fit_bins`` at that scale). Wire
    cost: ``D * F * 2 * max_size * 16`` bytes on the gather.

    ``exclude_masks`` (sequence, dict keyed by global block index, or a
    callable ``exclude_masks(i) -> mask | None`` for masks a multi-host
    caller recomputes lazily) carries the validator's imputed-cell
    masks, exactly as in ``fit_bins_blocked``.

    With ``runtime`` (``launch.multiproc.MultiHostMesh``) each process
    sketches only the block subsets of its own device shards — the
    block partition over shards is identical to the single-process
    call, so a memmap source pages in only the owning host's blocks —
    and the per-feature counts/compression/dtype metadata ride the
    payload's extra row so every host can reconstruct every shard's
    state from the gather alone. The merged edges are bitwise identical
    either way.
    """
    from ..data.pipeline import stream_blocks
    from .binning import (
        DEFAULT_SKETCH_SIZE, StreamingQuantileSketch, validate_n_bins,
    )

    n_bins = validate_n_bins(n_bins)
    if max_size is None:
        max_size = DEFAULT_SKETCH_SIZE
    blocks = stream_blocks(x, sample_block, what="fit_bins_sharded")
    n_features = int(np.asarray(blocks[0]).shape[1])
    axes = tuple(sample_axes)
    n_shards = 1
    for a in axes:
        n_shards *= int(mesh.shape[a])
    parts = np.array_split(np.arange(len(blocks)), n_shards)
    mine = (
        range(runtime.shard_lo, runtime.shard_hi) if runtime is not None
        else range(n_shards)
    )

    # Summaries never exceed 2 * max_size points (the sketch recompresses
    # past that), so every shard ships the same fixed-width payload. One
    # extra metadata row per feature carries [count_lo, count_hi,
    # compressed, dtype_char] so remote shards' states reconstruct from
    # the gather alone.
    width = 2 * max_size
    payloads = np.zeros((len(mine), n_features, width + 1, 4), np.uint32)
    for row, d in enumerate(mine):
        sk = StreamingQuantileSketch(n_features, max_size=max_size)
        for i in parts[d]:
            i = int(i)
            if exclude_masks is None:
                mask = None
            elif isinstance(exclude_masks, dict):
                mask = exclude_masks.get(i)
            elif callable(exclude_masks):
                mask = exclude_masks(i)
            else:
                mask = exclude_masks[i]
            sk.update(np.asarray(blocks[i]), exclude=mask)
        st = sk.state(pad_to=width)
        packed = np.ascontiguousarray(
            np.stack([st["values"], st["weights"]], axis=-1)
        )  # [F, width, 2] float64
        payloads[row, :, :width] = packed.view(np.uint32).reshape(
            n_features, width, 4
        )
        cnt = np.asarray(st["count"], np.uint64)
        payloads[row, :, width, 0] = (cnt & np.uint64(0xFFFFFFFF)).astype(
            np.uint32
        )
        payloads[row, :, width, 1] = (cnt >> np.uint64(32)).astype(np.uint32)
        payloads[row, :, width, 2] = np.asarray(st["compressed"], np.uint32)
        payloads[row, :, width, 3] = np.uint32(
            ord(np.dtype(st["value_dtype"]).char)
        )

    def _exchange(p_loc):
        g = p_loc  # [1, F, width + 1, 4] per shard
        for a in reversed(axes):
            g = jax.lax.all_gather(g, a, axis=0, tiled=True)
        return g

    gshape = (n_shards, n_features, width + 1, 4)
    p_dev = (
        runtime.put(
            payloads, gshape, P(axes),
            box=[(runtime.shard_lo, runtime.shard_hi)]
            + [(0, s) for s in gshape[1:]],
        )
        if runtime is not None else jnp.asarray(payloads)
    )
    gathered = jax.jit(jax.shard_map(
        _exchange, mesh=mesh,
        in_specs=(P(axes),),
        out_specs=P(),
        check_vma=False,
    ))(p_dev)
    gathered = np.ascontiguousarray(np.asarray(jax.device_get(gathered)))

    merged = None
    for d in range(n_shards):
        meta = gathered[d, :, width]
        unpacked = np.ascontiguousarray(gathered[d, :, :width]).view(
            np.float64
        ).reshape(n_features, width, 2)
        st = {
            "values": unpacked[..., 0],
            "weights": unpacked[..., 1],
            "count": (
                meta[:, 0].astype(np.uint64)
                | (meta[:, 1].astype(np.uint64) << np.uint64(32))
            ).astype(np.int64),
            "compressed": meta[:, 2].astype(np.bool_),
            "value_dtype": np.dtype(chr(int(meta[0, 3]))).str,
            "max_size": max_size,
        }
        sk_d = StreamingQuantileSketch.from_state(st)
        merged = sk_d if merged is None else merged.merge(sk_d)
    return merged.edges(n_bins)


# ---------------------------------------------------------------------------
# Multi-process training plane (launch.multiproc runtime)
# ---------------------------------------------------------------------------


def _dimred_streamed_multiproc(
    local_blocks, y_np, w_np, config, rng, runtime, *,
    sizes, quarantined=(), prefetch=2, feeder_opts=None,
    sample_axes=("data",), feature_axis="model",
):
    """``dimension_reduction_streamed`` on the multi-process plane.

    Each process folds only its local rows of every block into a
    ``[D, k, 1, F, B, C]`` histogram carry (same ``hist_spec`` layout as
    the growth driver); the final kernel psums across the sample shards
    — exact-integer DSI counts, so the accumulated root histogram, the
    gain ratios, and therefore the ``select_features`` mask are bitwise
    identical to the single-host sweep. The mask comes back replicated
    and is re-derived host-locally on every process.
    """
    from ..data.pipeline import BlockFeeder
    from .dimred import select_features

    sample_axes = tuple(sample_axes)
    mesh = runtime.mesh
    D = runtime.n_data_shards
    F = local_blocks[0].shape[1]
    cfg = config.resolved(F)
    k = w_np.shape[0]
    B, C = cfg.n_bins, cfg.n_classes
    offsets = np.concatenate([[0], np.cumsum(sizes)])
    pads = [(-n) % D for n in sizes]
    ms = [n + p for n, p in zip(sizes, pads)]
    hist_spec = P(sample_axes, None, None, feature_axis)

    feeder = BlockFeeder(
        local_blocks,
        placement=runtime.block_placement(
            ms, F, P(sample_axes, feature_axis)
        ),
        prefetch=prefetch, quarantined=quarantined, **(feeder_opts or {}),
    )

    def acc_kernel(hist_part, xb_loc, base_loc, w_loc):
        slot0 = jnp.zeros_like(w_loc, dtype=jnp.int32)
        h = hist_part[0] + level_histograms(
            xb_loc, base_loc, w_loc, slot0, n_slots=1, n_bins=B,
            backend=cfg.hist_backend,
        )
        return h[None]

    acc = jax.jit(jax.shard_map(
        acc_kernel, mesh=mesh,
        in_specs=(hist_spec, P(sample_axes, feature_axis), P(sample_axes),
                  P(None, sample_axes)),
        out_specs=hist_spec,
        check_vma=False,
    ))

    def final_kernel(hist_part):
        h = jax.lax.psum(hist_part[0], sample_axes)      # [k, 1, Fl, B, C]
        gr = multiway_gain_ratio(h[:, 0])                # [k, Fl]
        return jax.lax.all_gather(gr, feature_axis, axis=1, tiled=True)

    final = jax.jit(jax.shard_map(
        final_kernel, mesh=mesh, in_specs=(hist_spec,), out_specs=P(),
        check_vma=False,
    ))

    hist = runtime.zeros((D, k, 1, F, B, C), hist_spec, jnp.float32)
    try:
        for i, xb_b in zip(feeder.live_blocks, feeder.sweep()):
            o0, m = offsets[i], ms[i]
            lo, hi = runtime.local_row_range(m)
            nreal = max(min(hi, sizes[i]) - lo, 0)
            yb = np.zeros((hi - lo,), y_np.dtype)
            yb[:nreal] = y_np[o0 + lo:o0 + lo + nreal]
            ch = np.asarray(class_channels(jnp.asarray(yb), C))
            wb = np.zeros((k, hi - lo), np.float32)
            wb[:, :nreal] = w_np[:, o0 + lo:o0 + lo + nreal]
            hist = acc(
                hist, xb_b,
                runtime.put(ch, (m, C), P(sample_axes),
                            box=[(lo, hi), (0, C)]),
                runtime.put(wb, (k, m), P(None, sample_axes),
                            box=[(0, k), (lo, hi)]),
            )
    finally:
        feeder.close()
    gr = jnp.asarray(np.asarray(jax.device_get(final(hist))))
    return np.asarray(select_features(
        gr, rng, n_selected=cfg.n_selected, n_important=cfg.n_important
    ))


def train_prf_multiproc(
    x, y, config: ForestConfig, seed: int = 0, *,
    runtime=None,
    checkpoint_dir: Optional[str] = None,
    checkpoint_every: int = 1,
    checkpoint_keep: int = 3,
    resume_from: Optional[str] = None,
    on_level=None,
    feeder_opts: Optional[dict] = None,
    bad_block_policy: Optional[str] = "raise",
    sample_axes: Sequence[str] = ("data",),
    feature_axis: str = "model",
    sketch_max_size: Optional[int] = None,
):
    """End-to-end ``train_prf`` across ``jax.distributed`` processes.

    The whole pipeline — integrity screen, bin-edge fitting, binning,
    DSI bootstrap, dimension reduction, growth, OOB weighting — runs
    with every process touching only the rows its sample-axis shards
    own (``x`` is typically an ``np.memmap``; remote rows are never
    paged in, except that edge fitting reads the full blocks of this
    process's shard *subset* — the same block partition as
    ``fit_bins_sharded``). The trained model is **bitwise identical**
    to the single-process ``train_prf`` on the same ``(x, y, config,
    seed)``:

    * the per-block validator scans local rows and union-reduces the
      per-(block, column) bad-cell counts through one exact integer
      ``psum_hosts``, so every process reaches the same verdict (and
      the same typed ``DataIntegrityError`` under ``"raise"``); label
      screening runs on the globally-resident ``y`` identically
      everywhere;
    * edges come from per-shard quantile sketches merged bit-exactly;
    * the bootstrap/feature-mask PRNG draws are process-independent
      functions of ``seed``;
    * growth/dimred/OOB accumulate exact integer-valued f32 sums, so
      shard-order never matters.

    ``checkpoint_dir``/``resume_from`` go through the multi-process
    checkpoint protocol (process-0 manifest, per-host shard leaves);
    resuming under a different process count raises
    ``CheckpointTopologyError``. Regression with ``weighted_voting``
    is not wired on this plane yet and raises ``NotImplementedError``.
    ``sketch_max_size`` caps the per-shard quantile summary (wire and
    host cost of edge fitting scale with it; below the compression
    threshold edges are exact).
    """
    from ..data.pipeline import (
        BlockIssue, BlockValidator, DataIntegrityError, QuarantineReport,
    )
    from ..launch.multiproc import MultiHostMesh, MultiprocCheckpointManager
    from .api import PRFModel
    from .binning import apply_bins
    from .dimred import random_feature_mask

    config = config.resolved(x.shape[1])
    if config.sample_block <= 0:
        raise ValueError(
            "train_prf_multiproc needs config.sample_block > 0 — the "
            "multi-process plane is streaming-only (each process feeds "
            "its local rows of every sample block)"
        )
    if getattr(x, "ndim", None) != 2:
        raise ValueError(
            "train_prf_multiproc needs a 2-D [N, F] array-like source "
            "(np.memmap / np.ndarray) so every process can slice its own "
            f"rows; got {type(x).__name__}"
        )
    if runtime is None:
        runtime = MultiHostMesh(
            sample_axes=sample_axes, feature_axis=feature_axis
        )
    mesh = runtime.mesh
    sample_axes = tuple(sample_axes)
    D = runtime.n_data_shards
    N, F = int(x.shape[0]), int(x.shape[1])
    nb = config.sample_block
    sizes = [min(nb, N - o) for o in range(0, N, nb)]
    offsets = np.concatenate([[0], np.cumsum(sizes)])
    n_blocks = len(sizes)
    ms = [n + ((-n) % D) for n in sizes]
    windows = [runtime.local_row_range(m) for m in ms]
    y_host = np.asarray(y)

    def _local_view(i):
        """(view of x's local real rows of block i, their count)."""
        lo, hi = windows[i]
        o0 = offsets[i]
        nreal = max(min(hi, sizes[i]) - lo, 0)
        return x[o0 + lo:o0 + lo + nreal], nreal

    # ---- integrity screen (union-reduced across processes) ------------
    report = None
    cell_cols = None
    label_masks = {}
    quar = frozenset()
    if bad_block_policy not in (None, "off"):
        validator = BlockValidator(
            bad_block_policy, n_features=F,
            n_classes=None if config.regression else config.n_classes,
            regression=config.regression,
        )
        counts = np.zeros((n_blocks, F), np.int64)
        if np.issubdtype(np.asarray(x[:0]).dtype, np.inexact):
            for i in range(n_blocks):
                view, nreal = _local_view(i)
                if nreal:
                    counts[i] = (~np.isfinite(np.asarray(view))).sum(axis=0)
        cell_cols = runtime.psum_hosts(counts.ravel()).reshape(n_blocks, F)
        for i in range(n_blocks):
            lm = validator._label_mask(y_host[offsets[i]:offsets[i + 1]])
            if lm.any():
                label_masks[i] = lm
        report = QuarantineReport(
            policy=bad_block_policy, blocks_checked=n_blocks,
        )
        for i in range(n_blocks):
            bad_cells = int(cell_cols[i].sum())
            bad_labels = int(label_masks[i].sum()) if i in label_masks else 0
            if not bad_cells and not bad_labels:
                continue
            issue = BlockIssue(
                index=i, reason="nonfinite" if bad_cells else "label",
                columns=tuple(int(c) for c in np.flatnonzero(cell_cols[i])),
                bad_cells=bad_cells, bad_labels=bad_labels,
            )
            report.issues.append(issue)
            if bad_block_policy == "raise":
                raise DataIntegrityError(
                    issue.describe(), block_index=i,
                    columns=issue.columns, reason=issue.reason,
                )
            report.sanitized_cells += bad_cells
            report.sanitized_labels += bad_labels
            if bad_block_policy == "quarantine":
                report.quarantined.append(i)
        quar = frozenset(report.quarantined)
        if len(quar) == n_blocks:
            raise DataIntegrityError(
                f"every block quarantined ({n_blocks} of {n_blocks}) — "
                "nothing left to train on",
                reason="quarantine",
            )
        if label_masks:
            y_host = y_host.copy()
            for i, lm in label_masks.items():
                y_host[offsets[i]:offsets[i + 1]][lm] = 0
    good = [i for i in range(n_blocks) if i not in quar]
    flagged = (
        set() if cell_cols is None
        else {i for i in range(n_blocks) if cell_cols[i].any()}
    )

    # ---- bin edges (per-shard sketches over the good blocks) ----------
    good_views = [x[offsets[i]:offsets[i + 1]] for i in good]

    def _exclude(j):
        # Lazily recompute the imputed-cell mask of the j-th good block —
        # only the sketching shard ever pages the full block in.
        i = good[j]
        if i not in flagged:
            return None
        return ~np.isfinite(np.asarray(good_views[j]))

    edges = fit_bins_sharded(
        good_views, config.n_bins, mesh,
        sample_block=nb, sample_axes=sample_axes,
        max_size=sketch_max_size,
        exclude_masks=_exclude if flagged else None,
        runtime=runtime,
    )
    edges_dev = jnp.asarray(edges)

    # ---- bin the local rows of every block ----------------------------
    xb_local = []
    for i in range(n_blocks):
        lo, hi = windows[i]
        xbl = np.zeros((hi - lo, F), np.uint8)
        if i in quar:
            xb_local.append(xbl)             # placeholder, never swept
            continue
        view, nreal = _local_view(i)
        if nreal:
            xb = np.array(apply_bins(jnp.asarray(np.asarray(view)),
                                     edges_dev))
            if i in flagged:
                # apply_bins is element-wise, so binning the local row
                # slice matches the full-block binning bitwise; imputed
                # cells are forced to bin 0 exactly like the single-host
                # trainer.
                xb[~np.isfinite(np.asarray(view))] = 0
            xbl[:nreal] = xb
        xb_local.append(xbl)

    # ---- DSI bootstrap + feature selection (same PRNG everywhere) -----
    key = jax.random.PRNGKey(seed)
    k_boot, k_dim = jax.random.split(key)
    w_np = np.asarray(bootstrap_counts(k_boot, config.n_trees, N))
    if label_masks:
        bad_rows = np.zeros(N, dtype=bool)
        for i, lm in label_masks.items():
            bad_rows[offsets[i]:offsets[i + 1]][lm] = True
        w_np = np.where(bad_rows[None, :], 0, w_np)

    feature_mask = None
    if config.feature_mode == "importance" and not config.regression:
        feature_mask = _dimred_streamed_multiproc(
            xb_local, y_host, w_np, config, k_dim, runtime,
            sizes=sizes, quarantined=sorted(quar), feeder_opts=feeder_opts,
            sample_axes=sample_axes, feature_axis=feature_axis,
        )
    elif config.feature_mode == "random":
        feature_mask = np.asarray(random_feature_mask(
            k_dim, n_trees=config.n_trees, n_features=F,
            n_selected=config.n_selected,
        ))

    # ---- growth (the runtime-threaded mesh streamed driver) -----------
    manager = None
    if checkpoint_dir is not None:
        manager = MultiprocCheckpointManager(
            checkpoint_dir, keep=checkpoint_keep,
            save_interval=checkpoint_every, runtime=runtime,
        )
    y_grow = y_host if not config.regression else y_host.astype(np.float32)
    forest = grow_forest_streamed_sharded(
        xb_local, y_grow, w_np, config, mesh, feature_mask,
        sample_axes=sample_axes, feature_axis=feature_axis,
        manager=manager, resume_from=resume_from, on_level=on_level,
        feeder_opts=feeder_opts, quarantined=sorted(quar),
        runtime=runtime, block_sizes=sizes,
    )

    if config.weighted_voting:
        if config.regression:
            raise NotImplementedError(
                "weighted_voting for regression (OOB R^2) is not wired "
                "on the multi-process plane yet — set "
                "weighted_voting=False, or train single-process"
            )
        invalid = {}
        for i, lm in label_masks.items():
            if i in quar:
                continue
            lo, hi = windows[i]
            nreal = max(min(hi, sizes[i]) - lo, 0)
            m = np.zeros(hi - lo, bool)
            m[:nreal] = lm[lo:lo + nreal]
            if m.any():
                invalid[i] = m
        w = oob_accuracy_streamed_sharded(
            forest, xb_local, y_host, w_np, mesh,
            sample_axes=sample_axes, feature_axis=feature_axis,
            feeder_opts=feeder_opts, quarantined=sorted(quar),
            runtime=runtime, block_sizes=sizes,
            invalid_masks=invalid or None,
        )
        forest = dataclasses.replace(forest, tree_weight=w)

    return PRFModel(forest=forest, bin_edges=edges, quarantine=report)
