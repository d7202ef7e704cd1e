"""Weighted class-histogram construction — the T_GR workhorse (paper §4.2.1).

``level_histograms`` is the single entry point for every histogram the
trainer builds (single-host ``grow_forest``, the sharded
``_grow_sharded`` path, and dimension reduction). It dispatches between
two backends, selected by ``ForestConfig.hist_backend``:

* ``"segment_sum"`` — a per-tree, per-feature ``jax.ops.segment_sum``
  vmap. XLA-native scatter; the portable oracle.
* ``"pallas"`` — the fused MXU one-hot-matmul kernel
  (``kernels/gain_ratio``): one ``pallas_call`` emits the whole
  ``[tc, S, F, B, C]`` tensor for a chunk of trees, with the per-tree
  DSI weight multiply fused into the kernel and padding/masking for
  arbitrary ``N``/``F``. Runs in ``interpret`` mode off-TPU so the same
  code path is testable on CPU.
* ``"auto"`` — ``pallas`` when the default JAX backend is TPU, else
  ``segment_sum``.

Both backends apply the per-tree weight *inside* the per-tree step so
the ``[k, N, C]`` weighted-channel tensor is never materialized —
ensemble growth costs k*N weights, not k*N*C activations (the DSI
data-multiplexing property). ``packed=True`` (classification-shaped
one-hot channels only) folds the class index into the segment id, so
``segment_sum`` runs one scatter of the ``[k, N]`` weights per feature
instead of one per channel. It does not apply to the pallas kernel,
whose one contraction covers every channel.

The distributed path (core/distributed.py) calls the same function on
each device's (sample-shard x feature-shard) block and psums over the
sample axis.

The fused T_GR->T_NS path (core/engine.fused_level_scores and the
blocked dimension-reduction sweep in core/dimred.py) calls
``level_histograms`` on one ``hist_feature_slab``-wide column slice at a
time, so the full ``[tc, S, F, B, C]`` tensor never reaches HBM;
``blocked_level_histograms`` is the sample-axis analogue (a resumable
device-side accumulation over ``[sample_block, F]`` row blocks, used by
``ForestConfig.sample_block`` on the resident path). The host-streaming
data plane (``core.distributed.grow_forest_streamed``, on a one-device
mesh or a real one) runs the same accumulation across HOST-fed blocks
instead: one ``level_histograms``
call per block per level inside ``engine.stream_block_step``, summed
into a device-resident carry. Both orders are exact for integer-valued
DSI counts (every partial sum is an exact f32 integer below 2**24), so
resident, device-blocked, and host-streamed training agree bitwise.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ..kernels import default_interpret
from ..kernels.gain_ratio.kernel import multi_tree_hist_pallas
from .types import ForestConfig

BACKENDS = ("auto", "pallas", "segment_sum")


def resolve_backend(backend: str) -> str:
    """'auto' -> 'pallas' on TPU, 'segment_sum' elsewhere."""
    if backend not in BACKENDS:
        raise ValueError(f"hist_backend={backend!r} not in {BACKENDS}")
    if backend == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "segment_sum"
    return backend


def hist_feature_slab(N: int, F: int, S: int, B: int, C: int) -> int:
    """Feature-slab width for blocked histogram consumption.

    This is exactly the pallas hist kernel's own ``f_blk`` for the
    *full-F* problem, so per-slab histograms are bit-identical to
    column slices of the one-shot call: the kernel sees the same
    ``(n_blk, f_blk)`` blocks in the same order, just one
    feature-block-column at a time. (``segment_sum`` is per-feature
    independent, so it is trivially slab-invariant.)
    """
    from ..kernels.gain_ratio.kernel import choose_blocks

    return choose_blocks(N, F, S, B, C)[1]


@partial(
    jax.jit,
    static_argnames=("n_slots", "n_bins", "packed", "backend", "interpret"),
)
def level_histograms(
    x_binned: jnp.ndarray,      # [N, F] uint8
    base_channels: jnp.ndarray, # [N, C] per-sample channel data (unweighted)
    weights: jnp.ndarray,       # [k, N] per-tree in-bag weights (DSI counts)
    sample_slot: jnp.ndarray,   # [k, N] int32, -1 = parked
    *,
    n_slots: int,
    n_bins: int,
    packed: bool = False,
    backend: str = "auto",
    interpret: bool | None = None,
) -> jnp.ndarray:
    """hist[t,s,f,b,c] = sum_i w[t,i] * base[i,c] * [slot_i = s] * [x_if = b].

    ``base_channels`` is ``onehot(y)`` for classification or
    ``[1, y, y^2]`` for regression — same kernel either way. ``packed``
    (classification-shaped one-hot channels only) changes the
    ``segment_sum`` scatter layout and is ignored by the pallas kernel.

    ``interpret`` only affects the pallas backend; ``None`` means
    interpret off-TPU, compiled on TPU.

    Returns: [k, S, F, B, C] float32.
    """
    backend = resolve_backend(backend)
    if backend == "pallas":
        if interpret is None:
            interpret = default_interpret()
        return multi_tree_hist_pallas(
            x_binned, base_channels, weights, sample_slot,
            n_slots=n_slots, n_bins=n_bins, interpret=interpret,
        )

    k = weights.shape[0]
    C = base_channels.shape[-1]
    S, B = n_slots, n_bins
    parked = sample_slot < 0
    if packed:
        # Class index folded into the segment id: one scatter of the [k, N]
        # per-sample weights per feature.
        cls = jnp.argmax(base_channels, axis=-1).astype(jnp.int32)   # [N]
        wcls = base_channels.max(axis=-1)                            # per-sample scale
        seg0 = jnp.where(parked, S, sample_slot) * (B * C) + cls[None, :]
        updates, width = [weights * wcls[None, :]], B * C
    else:
        # One scatter per channel of the [k, N] weighted channel c.
        seg0 = jnp.where(parked, S, sample_slot) * B       # parked -> dump segment
        updates, width = [weights * base_channels[:, c][None, :] for c in range(C)], B
    step = C if packed else 1
    n_seg = (S + 1) * width

    def scatter(seg, u):                          # [N] ids, [N] values
        return jax.ops.segment_sum(u, seg, num_segments=n_seg)

    def per_feature(bins_f):                      # [N] bins of one feature
        seg = seg0 + bins_f.astype(jnp.int32)[None, :] * step          # [k, N]
        outs = [jax.vmap(scatter)(seg, u)[:, :S * width] for u in updates]
        return jnp.stack(outs, axis=-1).reshape(k, S, B, C)

    # Features are scanned, not vmapped: a vmap would broadcast the [k, N]
    # updates to [k, F, N] before the scatter. Every per-sample operand
    # keeps the samples minor (no [.., N, C] array, whose class axis
    # would pad to a full TPU lane tile).
    hist = jax.lax.map(per_feature, x_binned.T)             # [F, k, S, B, C]
    return jnp.transpose(hist, (1, 2, 0, 3, 4))


def blocked_level_histograms(
    x_binned: jnp.ndarray,      # [N, F] uint8
    base_channels: jnp.ndarray, # [N, C]
    weights: jnp.ndarray,       # [k, N]
    sample_slot: jnp.ndarray,   # [k, N] int32, -1 = parked
    *,
    n_slots: int,
    n_bins: int,
    sample_block: int,
    packed: bool = False,
    backend: str = "auto",
    interpret: bool | None = None,
) -> jnp.ndarray:
    """``level_histograms`` accumulated over ``[sample_block, F]`` row
    blocks — the resumable sample-axis carry of the T_GR stage.

    The histogram is a sum over samples, so feeding the kernel one row
    block at a time and adding the partial tensors is exact whenever the
    weighted counts are integer-valued (classification with DSI
    multiplicities — every partial sum stays an exact f32 integer below
    2**24), and agrees to float rounding for regression channels. The
    trailing remainder block is padded with parked samples
    (``slot = -1`` -> the kernels' dump segment), so any ``N`` works.

    Bounds the per-call sample working set to ``sample_block`` rows —
    the device-side half of the sample-block streaming path
    (``ForestConfig.sample_block``); the host-side half is
    ``core.distributed.grow_forest_streamed``.
    """
    N, F = x_binned.shape
    k = weights.shape[0]
    C = base_channels.shape[-1]
    nb = -(-N // sample_block)
    pad = nb * sample_block - N
    if pad:
        x_binned = jnp.pad(x_binned, ((0, pad), (0, 0)))
        base_channels = jnp.pad(base_channels, ((0, pad), (0, 0)))
        weights = jnp.pad(weights, ((0, 0), (0, pad)))
        sample_slot = jnp.pad(
            sample_slot, ((0, 0), (0, pad)), constant_values=-1
        )

    def body(i, acc):
        r0 = i * sample_block
        h = level_histograms(
            jax.lax.dynamic_slice_in_dim(x_binned, r0, sample_block, 0),
            jax.lax.dynamic_slice_in_dim(base_channels, r0, sample_block, 0),
            jax.lax.dynamic_slice_in_dim(weights, r0, sample_block, 1),
            jax.lax.dynamic_slice_in_dim(sample_slot, r0, sample_block, 1),
            n_slots=n_slots, n_bins=n_bins, packed=packed,
            backend=backend, interpret=interpret,
        )
        return acc + h

    init = jnp.zeros((k, n_slots, F, n_bins, C), jnp.float32)
    return jax.lax.fori_loop(0, nb, body, init)


# ---------------------------------------------------------------------------
# Sibling-subtraction histogram reuse (ForestConfig.hist_reuse)
# ---------------------------------------------------------------------------
#
# ``hist(parent) = hist(left) + hist(right)`` holds *bitwise* for the
# integer DSI counts (every partial sum is an exact f32 integer below
# 2**24 — the same argument that makes blocked accumulation exact), so a
# level's T_GR only needs to histogram the samples routed to the
# *smaller* child of each split; the sibling is ``parent - small``.
#
# Layout: splits admitted at the previous level carry dense ranks
# ``r in [0, n_splits)`` (``engine._rank_splits``) and their children
# occupy frontier slots ``2r`` / ``2r + 1``. The reuse path histograms
# into R = max_splits_per_level **rank segments** (samples in large
# slots are parked to the dump segment — the same masking machinery the
# early-exit scheduler uses for dead trees), which
#
# * halves the one-hot matmul width of the pallas T_GR kernel,
# * halves the scatter segment count of the segment_sum backend, and
# * halves the tensor the mesh plane's psum / psum_scatter moves
#   (``sibling_expand`` runs post-combine, so all shards agree).
#
# ``sibling_expand`` then rebuilds a full S-row tensor in *rank-paired*
# row order — rows [0, R) are the small children, rows [R, 2R) their
# subtraction-reconstructed siblings — NOT slot order: reordering the
# O(k*S) split descriptors after scoring (``sibling_perm``) is free,
# reordering the [k, S, F, B, C] tensor is a full extra memory pass.
# Unoccupied rows are exactly zero (invalid ranks contribute no samples
# and force ``large = 0``), matching what direct histogramming produces
# for empty slots — which is why reuse-on forests are bit-identical to
# reuse-off on every plane.


def sibling_segments(
    sample_slot: jnp.ndarray,    # [k, N] int32 frontier slots, -1 parked
    small_right: jnp.ndarray,    # [k, R] int32, 1 = right child is smaller
) -> jnp.ndarray:
    """Rank segment of each sample: ``slot // 2`` when the sample's slot
    is the *small* child of its pair, -1 (dump) otherwise.

    At level 0 the init cache (``small_right = 0``) makes slot 0 the
    "small" side of rank 0, so the whole dataset lands in segment 0 —
    the root histogram needs no special case.
    """
    R = small_right.shape[1]
    live = sample_slot >= 0
    s = jnp.where(live, sample_slot, 0)
    r = s // 2
    side = s - 2 * r
    sr = jnp.take_along_axis(small_right, jnp.minimum(r, R - 1), axis=1)
    keep = live & (side == sr) & (r < R)
    return jnp.where(keep, r, -1).astype(jnp.int32)


def sibling_perm(small_right: jnp.ndarray, n_slots: int) -> jnp.ndarray:
    """Slot -> paired-row permutation [k, S]: slot ``2r + side`` reads
    row ``r`` (small) or ``R + r`` (large); slots past ``2R`` read
    themselves (their rows are zero either way)."""
    k, R = small_right.shape
    s = jnp.arange(n_slots, dtype=jnp.int32)[None, :]
    r = jnp.minimum(s // 2, R - 1)
    side = s - 2 * r
    sr = jnp.take_along_axis(small_right, r, axis=1)
    pair = jnp.where(side == sr, r, R + r)
    return jnp.where(s < 2 * R, pair, s).astype(jnp.int32)


def sibling_expand(
    packed: jnp.ndarray,        # [k, R, F, B, C] small-child histograms
    cache_hist: jnp.ndarray,    # [k, S, F, B, C] previous level, paired rows
    cache_perm: jnp.ndarray,    # [k, S] previous level's slot -> row map
    parent: jnp.ndarray,        # [k, R] parent *slot* of each rank, -1 invalid
    n_slots: int,
) -> jnp.ndarray:
    """Rebuild the full level histogram from small-child segments:
    rows [0, R) = ``packed``, rows [R, 2R) = ``parent - packed`` (the
    large siblings), rows [2R, S) = zero. Returns [k, S, F, B, C] in
    rank-paired row order (see module comment; ``sibling_perm`` maps
    slots to rows)."""
    k, R = parent.shape
    valid = parent >= 0
    rows = jnp.take_along_axis(cache_perm, jnp.where(valid, parent, 0), axis=1)
    parent_h = jnp.take_along_axis(
        cache_hist, rows[:, :, None, None, None], axis=1
    )
    large = jnp.where(
        valid[:, :, None, None, None], parent_h - packed, 0.0
    )
    hist = jnp.concatenate([packed, large], axis=1)
    if 2 * R < n_slots:
        hist = jnp.pad(hist, ((0, 0), (0, n_slots - 2 * R)) + ((0, 0),) * 3)
    return hist[:, :n_slots]


def class_channels(y: jnp.ndarray, n_classes: int) -> jnp.ndarray:
    """onehot(y) -> [N, C] float32."""
    return jax.nn.one_hot(y, n_classes, dtype=jnp.float32)


def regression_channels(y: jnp.ndarray) -> jnp.ndarray:
    """[1, y, y^2] -> [N, 3] float32."""
    y = y.astype(jnp.float32)
    return jnp.stack([jnp.ones_like(y), y, y * y], axis=-1)


def label_channels(y: jnp.ndarray, config: ForestConfig) -> jnp.ndarray:
    """The ``[N, C]`` label channels every growth path histograms:
    ``regression_channels`` for a regression config, else the one-hot
    ``class_channels``."""
    if config.regression:
        return regression_channels(y)
    return class_channels(y, config.n_classes)
