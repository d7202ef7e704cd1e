"""Public PRF API — train / predict, paper-faithful pipeline.

    bin -> DSI bootstrap -> dimension reduction (Alg. 3.1)
        -> level-synchronous growth (Alg. 4.2) -> OOB weights (Eq. 8)

``train_prf`` is the single-process pipeline: resident, or streamed
through ``distributed.grow_forest_streamed`` when
``config.sample_block > 0`` (sample blocks fed from a NumPy/memmap
source — the full ``[N, F]`` matrix is never passed to one device
call). ``train_prf_multiproc`` runs the same pipeline across
``jax.distributed`` processes; ``repro.core.distributed`` holds the mesh
kernels all three build on.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .binning import apply_bins, fit_bins, fit_bins_blocked
from .dimred import (
    dimension_reduction, dimension_reduction_streamed, random_feature_mask,
)
from .distributed import (
    _dimred_streamed_multiproc, fit_bins_sharded, grow_forest_streamed,
    oob_accuracy_streamed_sharded,
)
from .dsi import bootstrap_counts
from .forest import (
    grow_forest, grow_forest_checkpointed, grown_leaves, with_leaves,
)
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
    forest = grow_forest_streamed(
        xb_local, y_grow, w_np, config, feature_mask, mesh=mesh,
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
