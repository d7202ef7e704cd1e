"""Public PRF API — train / predict, paper-faithful pipeline.

    bin -> DSI bootstrap -> dimension reduction (Alg. 3.1)
        -> level-synchronous growth (Alg. 4.2) -> OOB weights (Eq. 8)

``train_prf`` is the single-host path; ``repro.core.distributed`` offers
the mesh-sharded version with identical semantics, and
``grow_forest_streamed`` the host-streaming out-of-core growth driver
(sample blocks fed from a NumPy/memmap source — the full ``[N, F]``
matrix is never passed to one device call).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from .binning import apply_bins, fit_bins, fit_bins_blocked
from .dimred import (
    dimension_reduction, dimension_reduction_streamed, random_feature_mask,
)
from .dsi import bootstrap_counts
from .engine import (
    LocalPlane, _safe_mean, finalize_forest, init_forest, init_hist_cache,
    next_frontier, plan_level, resolve_hist_reuse, reuse_expand_scores,
    stream_block_step, write_level,
)
from .forest import (
    grow_forest, grow_forest_checkpointed, grown_leaves, with_leaves,
)
from .gain import SplitScores, level_scores, resolve_split_backend, sibling_plan
from .histograms import class_channels, regression_channels
from .tracing import host_span
from .types import Forest, ForestConfig
from .voting import (
    oob_accuracy, oob_accuracy_streamed, oob_r2, oob_r2_streamed, predict,
    predict_regression, predict_scores,
)


@dataclasses.dataclass
class PRFModel:
    """Trained model + the binning transform needed at inference.

    Prediction honors ``forest.config.predict_backend`` ("auto" |
    "pallas" | "xla"): the pallas backend runs the fused
    traversal+voting kernel (``kernels/tree_traverse``) that never
    materializes the ``[k, N, C]`` per-tree tensor; labels are
    identical across backends. For serving (batch bucketing, request
    aggregation, tree-sharded multi-device voting) wrap the model in
    ``repro.serving.PRFService``.

    ``quarantine`` is the data-integrity report of the training run
    (``data.pipeline.QuarantineReport``) when ``train_prf`` ran with a
    ``bad_block_policy``; ``None`` when validation was off. A clean
    report (``quarantine.clean``) certifies validation changed nothing.
    """

    forest: Forest
    bin_edges: np.ndarray
    quarantine: Optional[object] = None

    def _streams(self, x: np.ndarray) -> bool:
        """Out-of-core models (``config.sample_block > 0``) also predict
        per sample block — prediction is per-sample, so the blocked
        sweep is bit-identical to the resident call."""
        nb = self.forest.config.sample_block
        return nb > 0 and x.shape[0] > nb

    def _predict_blocks(self, x: np.ndarray, fn) -> np.ndarray:
        """Bin + evaluate one ``sample_block`` at a time: each binned
        block is consumed by ``fn`` before the next is built, so the
        full ``[N, F]`` matrix never becomes device-resident — only the
        per-sample outputs survive the sweep."""
        edges = jnp.asarray(self.bin_edges)
        nb = self.forest.config.sample_block
        return np.concatenate([
            np.asarray(
                fn(apply_bins(jnp.asarray(np.asarray(x[i:i + nb])), edges))
            )
            for i in range(0, x.shape[0], nb)
        ])

    def predict(self, x: np.ndarray) -> np.ndarray:
        regression = self.forest.config.regression
        if self._streams(x):
            fn = predict_regression if regression else predict
            return self._predict_blocks(x, partial(fn, self.forest))
        xb = apply_bins(jnp.asarray(np.asarray(x)), jnp.asarray(self.bin_edges))
        if regression:
            return np.asarray(predict_regression(self.forest, xb))
        return np.asarray(predict(self.forest, xb))

    def predict_scores(self, x: np.ndarray) -> np.ndarray:
        """Weighted-vote class scores [N, C] (classification only)."""
        if self.forest.config.regression:
            raise ValueError(
                "predict_scores is classification-only; use predict() for "
                "regression models"
            )
        if self._streams(x):
            return self._predict_blocks(x, partial(predict_scores, self.forest))
        xb = apply_bins(jnp.asarray(np.asarray(x)), jnp.asarray(self.bin_edges))
        return np.asarray(predict_scores(self.forest, xb))

    def accuracy(self, x: np.ndarray, y: np.ndarray) -> float:
        return float(np.mean(self.predict(x) == np.asarray(y)))

    def with_predict_backend(self, backend: str) -> "PRFModel":
        """Same model, different prediction backend (config is static)."""
        cfg = dataclasses.replace(self.forest.config, predict_backend=backend)
        return PRFModel(
            forest=dataclasses.replace(self.forest, config=cfg),
            bin_edges=self.bin_edges,
            quarantine=self.quarantine,
        )


def _checkpoint_manager(
    checkpoint_dir: Optional[str], checkpoint_every: int, checkpoint_keep: int
):
    if checkpoint_dir is None:
        return None
    from ..checkpoint.checkpoint import CheckpointManager

    return CheckpointManager(
        checkpoint_dir, keep=checkpoint_keep, save_interval=checkpoint_every
    )


@host_span("train")
def train_prf(
    x: np.ndarray,
    y: np.ndarray,
    config: ForestConfig,
    seed: int = 0,
    *,
    checkpoint_dir: Optional[str] = None,
    checkpoint_every: int = 1,
    checkpoint_keep: int = 3,
    resume_from: Optional[str] = None,
    on_level=None,
    feeder_opts: Optional[dict] = None,
    bad_block_policy: Optional[str] = "raise",
) -> PRFModel:
    """End-to-end PRF training on host data (paper §3 + §4 semantics).

    With ``config.sample_block > 0`` the whole pipeline — binning, DSI
    bootstrap, dimension reduction, growth, OOB weights — runs through
    the streaming data plane (``grow_forest_streamed`` and the blocked
    OOB/dimred carriers): ``x`` may be an ``np.memmap`` far larger than
    device memory, the full ``[N, F]`` matrix is never device-resident,
    and the resulting model is bit-identical to the resident path for
    classification (regression channels agree to float rounding).

    **Crash resume.** ``checkpoint_dir`` turns on per-level growth
    checkpointing (every ``checkpoint_every`` levels, ``checkpoint_keep``
    rotated atomic-rename checkpoints); ``resume_from`` restores the
    latest growth carry from that directory and continues. Everything
    before growth — binning, the DSI bootstrap, dimension reduction —
    is a deterministic function of ``(x, y, config, seed)`` and is
    recomputed on resume, so only the growth carry needs to be durable,
    and the resumed run's model is **bit-identical** to an
    uninterrupted one (tests/test_fault.py). An empty ``resume_from``
    directory means "no progress yet": training starts from scratch,
    so a crash-retry wrapper can always pass both knobs.
    ``on_level(level, _)`` fires after each completed (checkpointed)
    level; ``feeder_opts`` forwards retry/fault-injection knobs to the
    streamed path's ``BlockFeeder``. A corrupted or torn newest
    checkpoint in ``resume_from`` is skipped (CRC-verified restore walks
    back to the newest valid step) — resume still lands bit-identical.

    **Data integrity.** ``bad_block_policy`` runs a deterministic
    per-block validator (NaN/Inf cells, out-of-range labels, shape
    drift) over the training source before anything is binned:
    ``"raise"`` (default) fails fast with a typed ``DataIntegrityError``
    naming the block and columns; ``"sanitize"`` deterministically
    imputes (bad cells to bin 0, bad labels neutralized via zero DSI
    weight and excluded from OOB); ``"quarantine"`` drops poisoned
    blocks from every sweep (streamed path only — the resident dataset
    is one block) and records them in ``model.quarantine``; ``None`` /
    ``"off"`` disables validation. On clean data the returned model is
    **bitwise identical** with validation on or off.

    The call is the profiler host span ``prf.train``; on the resident
    path each stage inside it is a span of its own (``prf.screen``,
    ``prf.bin.fit``, ``prf.bin.apply``, ``prf.dsi``, ``prf.dimred``,
    ``prf.grow``, ``prf.oob``; ``core/tracing``).
    """
    config = config.resolved(x.shape[1])
    if jax.process_count() > 1:
        # Multi-process runtime (launch.multiproc.initialize was called):
        # every process runs the same train_prf call collectively, each
        # feeding only its local rows. Bitwise identical to the
        # single-process planes.
        from .distributed import train_prf_multiproc

        return train_prf_multiproc(
            x, y, config, seed,
            checkpoint_dir=checkpoint_dir, checkpoint_every=checkpoint_every,
            checkpoint_keep=checkpoint_keep, resume_from=resume_from,
            on_level=on_level, feeder_opts=feeder_opts,
            bad_block_policy=bad_block_policy,
        )
    if config.sample_block > 0:
        return _train_prf_streamed(
            x, y, config, seed,
            checkpoint=_checkpoint_manager(
                checkpoint_dir, checkpoint_every, checkpoint_keep
            ),
            resume_from=resume_from, on_level=on_level,
            feeder_opts=feeder_opts, bad_block_policy=bad_block_policy,
        )
    report, cell_mask, label_mask = None, None, None
    if bad_block_policy not in (None, "off"):
        from ..data.pipeline import DataIntegrityError, screen_blocks

        with host_span("screen"):
            blocks1, y_clean, cmasks, lmasks, report = screen_blocks(
                [np.asarray(x)], np.asarray(y), policy=bad_block_policy,
                n_features=x.shape[1],
                n_classes=None if config.regression else config.n_classes,
                regression=config.regression,
            )
        if not report.clean:
            if bad_block_policy == "quarantine":
                raise DataIntegrityError(
                    "bad_block_policy='quarantine' on the resident path "
                    "would drop the entire dataset (it is a single block) "
                    "— stream it with config.sample_block > 0, or use "
                    "'sanitize'",
                    block_index=0, reason="quarantine",
                )
            x, y = blocks1[0], y_clean
            cell_mask, label_mask = cmasks.get(0), lmasks.get(0)
    if config.resolved_bin_fit() == "blocked":
        # Blocked edge fitting on the resident path (bin_fit="blocked"):
        # same sketch as the streamed trainer, fed with views of x. The
        # validator's imputed cells are excluded from the sketch rather
        # than contributing their imputation constant.
        from ..data.pipeline import sample_blocks

        nb_fit = config.sample_block if config.sample_block > 0 else 65536
        with host_span("bin.fit"):
            edges = fit_bins_blocked(
                sample_blocks(x, nb_fit), config.n_bins,
                exclude_masks=(
                    None if cell_mask is None else sample_blocks(cell_mask, nb_fit)
                ),
            )
    else:
        with host_span("bin.fit"):
            edges = fit_bins(x, config.n_bins)
    with host_span("bin.apply"):
        xb_np = np.asarray(apply_bins(jnp.asarray(x), jnp.asarray(edges)))
        if cell_mask is not None:
            xb_np = xb_np.copy()
            xb_np[cell_mask] = 0                 # imputed cells -> bin 0
        xb = jnp.asarray(xb_np)
    y = jnp.asarray(y)
    key = jax.random.PRNGKey(seed)
    k_boot, k_dim = jax.random.split(key)

    with host_span("dsi"):
        weights = bootstrap_counts(k_boot, config.n_trees, x.shape[0])  # DSI §4.1.2
        if label_mask is not None:
            # Imputed-label samples get neutral (zero) weight in every tree.
            weights = jnp.where(jnp.asarray(label_mask)[None, :], 0, weights)

    feature_mask = None
    with host_span("dimred"):
        if config.feature_mode == "importance" and not config.regression:
            feature_mask = dimension_reduction(xb, y, weights, config, k_dim)  # §3.2
        elif config.feature_mode == "random":
            feature_mask = random_feature_mask(
                k_dim, n_trees=config.n_trees, n_features=x.shape[1],
                n_selected=config.n_selected,
            )                                                          # §3.1 RF

    y_grow = y if not config.regression else y.astype(jnp.float32)
    with host_span("grow"):
        if checkpoint_dir is not None or resume_from is not None:
            forest = grow_forest_checkpointed(
                xb, y_grow, weights, config, feature_mask,
                manager=_checkpoint_manager(
                    checkpoint_dir, checkpoint_every, checkpoint_keep
                ),
                resume_from=resume_from, on_level=on_level,
            )                                                          # §4.2
        else:
            forest = grow_forest(xb, y_grow, weights, config, feature_mask)  # §4.2

    if config.weighted_voting:                                         # §3.3
        with host_span("oob"):
            xb_o, y_o, w_o = xb, y, weights
            if label_mask is not None:
                # Zero-weight == out-of-bag, so imputed-label samples would
                # otherwise score every tree against a made-up label — drop
                # them from the Eq. 8 evaluation entirely.
                kidx = jnp.asarray(np.flatnonzero(~label_mask))
                xb_o = jnp.take(xb, kidx, axis=0)
                y_o = jnp.take(y, kidx, axis=0)
                w_o = jnp.take(weights, kidx, axis=1)
                leaves = grown_leaves(forest, xb)
                if leaves is not None:
                    forest = with_leaves(
                        forest, xb_o, jnp.take(leaves, kidx, axis=1)
                    )
            w = (
                oob_r2(forest, xb_o, y_o.astype(jnp.float32), w_o)
                if config.regression
                else oob_accuracy(forest, xb_o, y_o, w_o)
            )
        forest = dataclasses.replace(forest, tree_weight=w)

    return PRFModel(forest=forest, bin_edges=edges, quarantine=report)


# ---------------------------------------------------------------------------
# Host-streaming out-of-core training (the streaming data plane)
# ---------------------------------------------------------------------------


def _channels(y: jnp.ndarray, config: ForestConfig) -> jnp.ndarray:
    return (
        regression_channels(y)
        if config.regression
        else class_channels(y, config.n_classes)
    )


def _train_prf_streamed(
    x: np.ndarray, y: np.ndarray, config: ForestConfig, seed: int,
    *,
    checkpoint=None,
    resume_from: Optional[str] = None,
    on_level=None,
    feeder_opts: Optional[dict] = None,
    bad_block_policy: Optional[str] = "raise",
) -> PRFModel:
    """``train_prf`` over the streaming data plane (never re-validates
    shapes against a device-resident ``[N, F]`` matrix — there is none).

    Binning edges are fit out-of-core too (``bin_fit="auto"`` resolves
    to the blocked path here): per-block sorted summaries merge in a
    ``StreamingQuantileSketch``, so edge fitting costs O(block) +
    O(F * sketch) host memory and never materializes the raw source —
    bitwise identical to the resident ``np.quantile`` below the sketch's
    compression threshold. Everything downstream — the binned blocks,
    dimension reduction, growth, OOB weights, and the model's own
    predictions — moves per ``sample_block`` rows.

    **Integrity screen.** With ``bad_block_policy`` set, every raw block
    is validated *before* edge fitting (one NaN would otherwise poison
    every ``np.quantile`` edge): sanitized cells are imputed then forced
    to bin 0, sanitized labels get zero DSI weight and are excluded from
    OOB, and quarantined blocks are excluded from edge fitting, dimred,
    the growth sweep (the feeder never transfers them), and OOB — all
    decided once, deterministically, so rerunning reproduces the same
    model. When the screen finds nothing, every downstream input is the
    untouched original — bitwise identical to validation off.
    """
    nb = config.sample_block
    N = x.shape[0]
    raw_blocks = [np.asarray(x[i:i + nb]) for i in range(0, N, nb)]
    y_host = np.asarray(y)
    report = None
    cell_masks, label_masks = {}, {}
    quar = frozenset()
    if bad_block_policy not in (None, "off"):
        from ..data.pipeline import DataIntegrityError, screen_blocks

        raw_blocks, y_host, cell_masks, label_masks, report = screen_blocks(
            raw_blocks, y_host, policy=bad_block_policy,
            n_features=x.shape[1],
            n_classes=None if config.regression else config.n_classes,
            regression=config.regression,
        )
        quar = frozenset(report.quarantined)
        if len(quar) == len(raw_blocks):
            raise DataIntegrityError(
                f"every block quarantined ({len(raw_blocks)} of "
                f"{len(raw_blocks)}) — nothing left to train on",
                reason="quarantine",
            )
    dirty = report is not None and not report.clean
    good = [i for i in range(len(raw_blocks)) if i not in quar]

    if config.resolved_bin_fit() == "blocked":
        # Out-of-core edge fitting (the default whenever sample_block > 0):
        # per-block sorted summaries merged in a StreamingQuantileSketch —
        # O(block) + O(F * sketch) host memory, never a full pass over the
        # raw source. Quarantined blocks never enter the sketch, and
        # sanitized blocks contribute only their finite original cells
        # (the validator's imputed-cell masks become exclusion masks
        # instead of a full np.concatenate of the good blocks).
        edges = fit_bins_blocked(
            (raw_blocks[i] for i in good), config.n_bins,
            exclude_masks={
                j: cell_masks[i] for j, i in enumerate(good) if i in cell_masks
            },
        )
    elif dirty:
        # bin_fit="exact" on dirty data: edges from screened data only —
        # this is the one remaining full-pass concatenate, kept verbatim
        # for strict compatibility with the pre-sketch behavior.
        edges = fit_bins(
            np.concatenate([raw_blocks[i] for i in good]), config.n_bins
        )
    else:
        edges = fit_bins(x, config.n_bins)
    edges_dev = jnp.asarray(edges)
    # Binned uint8 blocks stay HOST-resident (4-8x smaller than the raw
    # floats); each level sweep feeds them to the device one at a time.
    xb_blocks = []
    for i, rb in enumerate(raw_blocks):
        xb = np.asarray(apply_bins(jnp.asarray(rb), edges_dev))
        if i in cell_masks:
            xb = np.array(xb)
            xb[cell_masks[i]] = 0            # imputed cells -> bin 0
        xb_blocks.append(xb)
    y = jnp.asarray(y_host)
    key = jax.random.PRNGKey(seed)
    k_boot, k_dim = jax.random.split(key)

    weights = bootstrap_counts(k_boot, config.n_trees, N)          # DSI §4.1.2
    if label_masks:
        # Imputed-label samples get neutral (zero) weight in every tree.
        bad_rows = np.zeros(N, dtype=bool)
        for i, m in label_masks.items():
            bad_rows[i * nb:i * nb + m.shape[0]][m] = True
        weights = jnp.where(jnp.asarray(bad_rows)[None, :], 0, weights)

    def _drop_quarantined(blocks, y_dev, w_dev):
        """Filter quarantined blocks out of a (blocks, y, weights) feed,
        keeping labels/weights aligned with the surviving blocks."""
        if not quar:
            return blocks, y_dev, w_dev
        ys = jnp.concatenate(
            [y_dev[i * nb:i * nb + blocks[i].shape[0]] for i in good]
        )
        ws = jnp.concatenate(
            [w_dev[:, i * nb:i * nb + blocks[i].shape[0]] for i in good],
            axis=1,
        )
        return [blocks[i] for i in good], ys, ws

    feature_mask = None
    if config.feature_mode == "importance" and not config.regression:
        dr_blocks, dr_y, dr_w = _drop_quarantined(xb_blocks, y, weights)
        feature_mask = dimension_reduction_streamed(                   # §3.2
            dr_blocks, dr_y, dr_w, config, k_dim
        )
    elif config.feature_mode == "random":
        feature_mask = random_feature_mask(
            k_dim, n_trees=config.n_trees, n_features=x.shape[1],
            n_selected=config.n_selected,
        )                                                              # §3.1 RF

    y = y if not config.regression else y.astype(jnp.float32)
    forest = grow_forest_streamed(
        xb_blocks, y, weights, config, feature_mask,
        manager=checkpoint, resume_from=resume_from, on_level=on_level,
        feeder_opts=feeder_opts, quarantined=sorted(quar),
    )                                                                  # §4.2

    if config.weighted_voting:                                         # §3.3
        if dirty:
            # OOB over surviving blocks and rows only: quarantined
            # blocks are gone, and imputed-label rows (zero weight ==
            # out-of-bag everywhere) must not score trees against a
            # made-up label.
            w_host = np.asarray(weights)
            y_oob = y_host if not config.regression else \
                y_host.astype(np.float32)
            o_blocks, o_y, o_w = [], [], []
            for i in good:
                o0, n_i = i * nb, xb_blocks[i].shape[0]
                keep = (
                    ~label_masks[i] if i in label_masks
                    else np.ones(n_i, dtype=bool)
                )
                if not keep.any():
                    continue
                o_blocks.append(xb_blocks[i][keep])
                o_y.append(y_oob[o0:o0 + n_i][keep])
                o_w.append(w_host[:, o0:o0 + n_i][:, keep])
            oy = jnp.asarray(np.concatenate(o_y))
            ow = jnp.asarray(np.concatenate(o_w, axis=1))
            w = (
                oob_r2_streamed(forest, o_blocks, oy.astype(jnp.float32), ow)
                if config.regression
                else oob_accuracy_streamed(forest, o_blocks, oy, ow)
            )
        else:
            w = (
                oob_r2_streamed(
                    forest, xb_blocks, y.astype(jnp.float32), weights
                )
                if config.regression
                else oob_accuracy_streamed(forest, xb_blocks, y, weights)
            )
        forest = dataclasses.replace(forest, tree_weight=w)

    return PRFModel(forest=forest, bin_edges=edges, quarantine=report)


@partial(jax.jit, static_argnames=("config",))
def _stream_init(level0_hist, config):
    """Root node from the accumulated level-0 histogram: at level 0
    every sample sits in slot 0, so one feature's bin marginal IS the
    [k, C] root class counts — no extra pass over the blocks."""
    root_counts = level0_hist[:, 0, 0].sum(axis=1)
    forest = init_forest(config)
    forest = dataclasses.replace(
        forest, class_counts=forest.class_counts.at[:, 0].set(root_counts)
    )
    if config.regression:
        forest = dataclasses.replace(
            forest, value=forest.value.at[:, 0].set(_safe_mean(root_counts))
        )
    return forest


@partial(jax.jit, static_argnames=("config", "route"))
def _stream_block_step(
    hist_acc, xb_b, base_b, w_b, slot_b, slot_node, split_rank, scores,
    config, route, small_right=None,
):
    """The fused route+histogram pass for one block on the local plane —
    see ``engine.stream_block_step``. ONE jitted call, ONE read of the
    block per level. ``small_right`` switches the block into the packed
    sibling-subtraction histogram (``config.hist_reuse``)."""
    return stream_block_step(
        hist_acc, xb_b, base_b, w_b, slot_b, slot_node, split_rank, scores,
        config, LocalPlane(), route=route, small_right=small_right,
    )


@partial(jax.jit, static_argnames=("config",))
def _stream_plan_write(forest, slot_node, hist, feature_mask, level, config):
    """T_NS + node writes for one level, from the accumulated histogram.
    Runs the same plan/write/frontier pieces as the resident engine."""
    scores, n_node = level_scores(
        hist, feature_mask, regression=config.regression,
        backend=resolve_split_backend(config.split_backend),
    )
    split_rank, is_split, child_base = plan_level(
        scores, n_node, slot_node, config, level
    )
    forest = write_level(
        forest, slot_node, split_rank, is_split, child_base, scores, config
    )
    new_slot_node = next_frontier(is_split, child_base, config.frontier)
    return forest, scores, split_rank, new_slot_node


@partial(jax.jit, static_argnames=("config",))
def _stream_plan_write_reuse(
    forest, slot_node, packed_h, cache, feature_mask, level, config,
):
    """Reuse-mode ``_stream_plan_write``: the level's accumulated packed
    (small-child) histogram is expanded against the cache
    (``parent - small``), scored in paired-row order, permuted back to
    slots, and the refreshed cache — this level's paired tensor plus the
    next level's small-side plan — rides out with the level plan."""
    scores, n_node, hist2, perm = reuse_expand_scores(
        packed_h, cache, feature_mask, config
    )
    split_rank, is_split, child_base = plan_level(
        scores, n_node, slot_node, config, level
    )
    forest = write_level(
        forest, slot_node, split_rank, is_split, child_base, scores, config
    )
    new_slot_node = next_frontier(is_split, child_base, config.frontier)
    parent, small_right = sibling_plan(
        scores, split_rank, is_split,
        n_ranks=config.max_splits_per_level, regression=config.regression,
    )
    new_cache = {
        "hist": hist2, "perm": perm,
        "parent": parent, "small_right": small_right,
    }
    return forest, scores, split_rank, new_slot_node, new_cache


def _stream_setup(
    x_binned, y, weights, config: ForestConfig, prefetch: int,
    feeder_opts: Optional[dict] = None,
    quarantined: Sequence[int] = (),
):
    """Shared host-side setup of the streaming growth drivers: validated
    block list and a ``BlockFeeder`` over the blocks. ``feeder_opts``
    forwards retry/backoff/fault-injection/validator knobs to the
    feeder; ``quarantined`` block indices are dropped from every sweep
    (never transferred to a device)."""
    from ..data.pipeline import BlockFeeder, stream_blocks

    y_np = np.asarray(y)
    w_np = np.asarray(weights, dtype=np.float32)
    blocks = stream_blocks(
        x_binned, config.sample_block, what="grow_forest_streamed",
        n_y=y_np.shape[0], n_w=w_np.shape[1],
    )
    sizes = [b.shape[0] for b in blocks]
    offsets = np.concatenate([[0], np.cumsum(sizes)])
    if config.regression:
        y_np = y_np.astype(np.float32)
    feeder = BlockFeeder(
        blocks, prefetch=prefetch, quarantined=quarantined,
        **(feeder_opts or {}),
    )
    return feeder, y_np, w_np, sizes, offsets


def _stream_state_like(sizes, config: ForestConfig, hist_width: int = 0):
    """Structure template for the streamed growth checkpoint: the
    host-driven driver's full inter-level carry. ``scores``/``split_rank``
    must be part of it — the streaming plane fuses each level's routing
    into the NEXT level's block sweep, so resuming at level L+1 needs
    level L's plan, not just the forest and frontier.

    ``hist_width > 0`` adds the sibling-subtraction cache (the plane's
    post-combine feature width) — the reuse carry must be durable or a
    resumed run would lose the subtraction baseline. With reuse off the
    entry is ``None``, an *empty* pytree child, so off-mode templates
    (and therefore existing checkpoints) are byte-compatible."""
    k, S = config.n_trees, config.frontier
    C = 3 if config.regression else config.n_classes
    return {
        "forest": init_forest(config),
        "slot_node": jnp.zeros((k, S), jnp.int32),
        "scores": SplitScores(
            jnp.zeros((k, S), jnp.float32),
            jnp.zeros((k, S), jnp.int32),
            jnp.zeros((k, S), jnp.int32),
            jnp.zeros((k, S, C), jnp.float32),
            jnp.zeros((k, S, C), jnp.float32),
        ),
        "split_rank": jnp.zeros((k, S), jnp.int32),
        "slots": [jnp.zeros((k, n), jnp.int32) for n in sizes],
        "level": jnp.asarray(0, jnp.int32),
        "hist_cache": (
            init_hist_cache(config, hist_width) if hist_width > 0 else None
        ),
    }


def grow_forest_streamed(
    x_binned: Union[np.ndarray, Sequence[np.ndarray]],
    y: np.ndarray,
    weights: np.ndarray,
    config: ForestConfig,
    feature_mask: Optional[np.ndarray] = None,
    *,
    prefetch: int = 2,
    manager=None,
    resume_from: Optional[str] = None,
    on_level=None,
    feeder_opts: Optional[dict] = None,
    quarantined: Sequence[int] = (),
) -> Forest:
    """Out-of-core ``grow_forest`` over the async streaming data plane.

    ``x_binned`` is either a host array / ``np.memmap`` of binned
    features ``[N, F]`` (sliced into ``config.sample_block``-row views —
    no copy; ``sample_block > 0`` is required so the full matrix can
    never silently become one device block) or an explicit sequence of
    ``[Nb, F]`` blocks.

    Data-plane accounting (each device call only ever sees one block):

    * **one read per level** — per block per level, ONE jitted call
      (``engine.stream_block_step``) routes the block's samples from
      the previous level's frontier and immediately folds them into
      this level's histogram carry, so the route and histogram passes
      share a single host->device feed of the block;
    * **async double-buffering** — a ``BlockFeeder`` thread keeps
      ``prefetch`` block copies in flight, so block ``i+1``'s
      host->device transfer overlaps block ``i``'s histogram
      (``prefetch=0`` restores the synchronous feed);
    * **pinned per-block constants** — label channels and DSI weights
      are uploaded once for the whole growth, not once per level, and
      the per-sample slot table stays device-resident across levels
      (no host round-trip per block per level).

    Per level, one jitted call then scores + writes the level with the
    engine's shared ``plan_level`` / ``write_level`` / ``next_frontier``
    pieces. Root class counts come for free from the level-0 histogram
    (every sample sits in slot 0). Device memory: the ``[N, F]`` bin
    matrix — the dominant term for realistic F — is never resident
    (one ``sample_block * F`` block at a time, plus the
    ``k*S*F*B*C`` histogram carry), but the pinned weight/channel/slot
    operands DO scale with N: ``(2k + C) * N`` f32/int32 words stay on
    device for the whole growth (the price of feeding them zero times
    per level instead of twice). With k trees per host ≪ F features
    that is a small fraction of the streamed data; for very large
    ensembles, shard trees across hosts before streaming.

    DSI counts are integer-valued, so the blocked accumulation is
    bit-exact for classification: the result equals the resident
    ``grow_forest`` forest array for array (tests/test_engine.py pins
    this across >= 4 blocks, with and without prefetch). Regression
    channels agree to float rounding. Host-side early exit stops the
    level loop as soon as every tree's frontier is empty (always on —
    the loop is host-driven and the forests are identical either way;
    ``config.early_exit`` only gates the device-side ``lax.while_loop``).

    **Checkpointing** mirrors ``grow_forest_checkpointed``: ``manager``
    saves the driver's full inter-level carry (forest, frontier, level
    plan, per-block slot tables — see ``_stream_state_like``) after
    each level; ``resume_from`` restores the newest *CRC-verified*
    carry (``checkpoint.restore_latest_valid`` — a corrupted or torn
    newest step is skipped, costing recompute of the affected levels,
    never a poisoned model) and the level loop continues where it
    stopped, producing the bit-identical forest.
    ``on_level(level, forest)`` fires after each completed level's
    checkpoint.

    **Quarantine.** ``quarantined`` block indices (plus any the feeder's
    own ``validator`` flags — forward one via ``feeder_opts``) are
    dropped from every level sweep: never transferred, never routed,
    never histogrammed. Their slot-table entries stay as zeros in the
    checkpoint carry, so the carry structure — and therefore resume —
    is independent of which blocks were quarantined.
    """
    feeder, y_np, w_np, sizes, offsets = _stream_setup(
        x_binned, y, weights, config, prefetch, feeder_opts, quarantined
    )

    k, S = config.n_trees, config.frontier
    F = feeder.blocks[0].shape[1]
    B = config.n_bins
    C = 3 if config.regression else config.n_classes
    mask_dev = None if feature_mask is None else jnp.asarray(feature_mask)
    # Sibling-subtraction reuse: blocks scatter into R rank segments
    # instead of S slots (the per-level carry is half the tensor) and
    # the plan step subtracts large children from the durable cache.
    reuse = resolve_hist_reuse(config, F)
    n_rows = config.max_splits_per_level if reuse else S

    # Per-block constants: pinned on device ONCE for the whole growth.
    # Quarantined blocks get no pins — nothing of theirs ever lands on
    # a device.
    live = set(feeder.live_blocks)
    base_dev, w_dev = [], []
    for i in range(len(feeder)):
        if i not in live:
            base_dev.append(None)
            w_dev.append(None)
            continue
        o0, o1 = offsets[i], offsets[i + 1]
        base_dev.append(_channels(feeder.pin(y_np[o0:o1]), config))
        w_dev.append(feeder.pin(w_np[:, o0:o1]))

    state = None
    if resume_from is not None:
        from ..checkpoint.checkpoint import restore_latest_valid

        restored = restore_latest_valid(
            _stream_state_like(sizes, config, F if reuse else 0), resume_from
        )
        if restored is not None:
            state, _ = restored
    if state is not None:
        forest, slot_node = state["forest"], state["slot_node"]
        scores, split_rank = state["scores"], state["split_rank"]
        slot_dev, start = list(state["slots"]), int(state["level"])
        cache = state["hist_cache"]
    else:
        # The per-sample frontier table: device-resident across levels.
        slot_dev = [jnp.zeros((k, n), jnp.int32) for n in sizes]
        slot_node = jnp.full((k, S), -1, jnp.int32).at[:, 0].set(0)
        forest, scores, split_rank = None, None, None
        cache = init_hist_cache(config, F) if reuse else None
        start = 0

    def level_sweep(route: bool):
        hist = jnp.zeros((k, n_rows, F, B, C), jnp.float32)
        for i, xb_b in zip(feeder.live_blocks, feeder.sweep()):
            hist, slot_dev[i] = _stream_block_step(
                hist, xb_b, base_dev[i], w_dev[i], slot_dev[i], slot_node,
                split_rank if route else None, scores if route else None,
                config, route,
                cache["small_right"] if reuse else None,
            )
        return hist

    try:
        for level in range(start, config.max_depth):
            if not np.any(np.asarray(slot_node) >= 0):
                break                               # every frontier is empty
            hist = level_sweep(route=level > 0)
            if forest is None:
                forest = _stream_init(hist, config)  # root node, free at level 0
            if reuse:
                forest, scores, split_rank, slot_node, cache = (
                    _stream_plan_write_reuse(
                        forest, slot_node, hist, cache, mask_dev,
                        jnp.asarray(level, jnp.int32), config,
                    )
                )
            else:
                forest, scores, split_rank, slot_node = _stream_plan_write(
                    forest, slot_node, hist, mask_dev,
                    jnp.asarray(level, jnp.int32), config,
                )
            if manager is not None:
                manager.maybe_save({
                    "forest": forest, "slot_node": slot_node,
                    "scores": scores, "split_rank": split_rank,
                    "slots": slot_dev,
                    "level": jnp.asarray(level + 1, jnp.int32),
                    "hist_cache": cache,
                }, level + 1)
            if on_level is not None:
                on_level(level + 1, forest)

        if forest is None:          # max_depth == 0: root node only
            forest = _stream_init(level_sweep(route=False), config)
    finally:
        feeder.close()
    return finalize_forest(forest)
