"""Core datatypes for the Parallel Random Forest (PRF).

The forest is stored as flat, fixed-shape arrays (a *node pool*) so that
training and inference are pure XLA programs with static shapes:

* every tree owns ``max_nodes = 1 + 2 * frontier * depth`` pool slots;
* level ``L`` always allocates its children inside the pool range
  ``[1 + 2*frontier*L, 1 + 2*frontier*(L+1))`` — allocation is a pure
  index computation, no dynamic counters cross a ``lax.scan`` boundary.

This mirrors the paper's task DAG: one pool "band" per DAG stage.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp


def _pytree_dataclass(cls):
    """Register a dataclass as a JAX pytree (fields = leaves, config aux)."""
    fields = [f.name for f in dataclasses.fields(cls) if not f.metadata.get("static")]
    static = [f.name for f in dataclasses.fields(cls) if f.metadata.get("static")]

    def flatten(obj):
        return tuple(getattr(obj, n) for n in fields), tuple(getattr(obj, n) for n in static)

    def unflatten(aux, leaves):
        return cls(**dict(zip(fields, leaves)), **dict(zip(static, aux)))

    jax.tree_util.register_pytree_node(cls, flatten, unflatten)
    return cls


def static_field(**kw):
    return dataclasses.field(metadata={"static": True}, **kw)


@dataclasses.dataclass(frozen=True)
class ForestConfig:
    """Hyper-parameters of the PRF algorithm (paper §3–§4)."""

    n_trees: int = 32                 # k — ensemble size
    max_depth: int = 8                # levels of splitting
    n_bins: int = 64                  # histogram bins per feature (TPU adaptation)
    n_classes: int = 2                # C
    max_frontier: int = 0             # beam width; 0 => full 2**max_depth
    min_samples_split: int = 2
    min_gain: float = 1e-7            # minimal gain ratio to split
    # --- paper §3.2: dimension reduction ----------------------------------
    # "importance": paper's Alg. 3.1 (top-k_imp by VI + random rest)
    # "random":     Breiman RF — m features per tree, uniformly (paper §3.1)
    # "all":        no per-tree feature restriction (bagged trees)
    feature_mode: str = "importance"
    n_important: int = 0              # paper's k  (0 => ceil(sqrt(m_selected)))
    n_selected: int = 0               # paper's m  (0 => ceil(sqrt(M)))
    # --- paper §3.3: weighted voting --------------------------------------
    weighted_voting: bool = True
    soft_voting: bool = False         # Majority[w_i * h_i(x)] (hard) vs prob-weighted
    # --- task-parallel execution knobs (§4.2) ------------------------------
    tree_chunk: int = 0               # trees processed per level-step (0 => all)
    # Early-exit scheduling (paper §4.2: schedulers only dispatch the
    # T_GR/T_NS tasks that exist): the growth loop is a ``lax.while_loop``
    # that stops as soon as every tree's frontier is empty, and trees
    # whose frontiers died contribute zero-weight (masked) work inside
    # each tree_chunk task group. Off => fixed ``max_depth`` iterations.
    # Either way the resulting Forest arrays are bit-identical (the pad
    # slot is sanitized after growth), so this is purely a scheduling knob.
    early_exit: bool = True
    # Sample-block streaming: > 0 => level histograms are accumulated over
    # [sample_block, F] row blocks instead of one [N, F] pass, bounding
    # the per-call sample working set (resumable hist carry, mirroring
    # fused_vote_scores' chunk carry on the predict side). 0 => one pass.
    # Integer-valued DSI counts make the blocked accumulation bit-exact
    # for classification; regression channels agree to float rounding.
    # ``train_prf`` dispatches the WHOLE pipeline (binning, dimred,
    # growth, OOB weights, prediction) through the streaming data plane
    # when this is > 0 — the host-streaming ``grow_forest_streamed``
    # driver (core/distributed.py) feeds blocks of this size from a
    # NumPy/memmap source with async double-buffered host->device copies
    # (data.pipeline.BlockFeeder), so the full [N, F] matrix is never
    # device-resident.
    sample_block: int = 0
    # Bin-edge fitting strategy (core/binning.py):
    #   "exact"   — one np.quantile over the full raw source (copies +
    #               sorts [N, F] in host RAM; the original behavior).
    #   "blocked" — StreamingQuantileSketch over sample blocks: O(block)
    #               + O(F * sketch) memory, bitwise identical to "exact"
    #               below the sketch's compression threshold and
    #               deterministic always.
    #   "auto"    — "blocked" whenever sample_block > 0 (the streamed
    #               trainer must not take a full pass over a memmap),
    #               "exact" otherwise.
    bin_fit: str = "auto"
    regression: bool = False
    # --- §Perf optimizations (beyond-paper; see EXPERIMENTS.md §Perf) ------
    packed_hist: bool = False         # segment_sum: class index in segment ids
    hist_reduce: str = "psum"         # psum | psum_scatter (distributed T_GR)
    # Sibling-subtraction histogram reuse:
    # between levels the engine carries the previous level's per-slot
    # histograms, histograms ONLY samples routed to the *smaller* child
    # of each split, and reconstructs every large child as
    # ``parent - small_sibling`` — halving T_GR's histogram build (and,
    # on the mesh plane, the psum/psum_scatter volume: only the packed
    # small-child partials cross the wire). Exact for classification
    # (integer DSI counts: ``hist(parent) = hist(left) + hist(right)``
    # holds bitwise below 2**24), so "on" forests are bit-identical to
    # "off" on every plane; regression channels ([1, y, y^2] f32 sums)
    # only agree to float rounding, so:
    #   "auto" — reuse for classification, off for regression;
    #   "on"   — always (regression is an explicit tolerance opt-in);
    #   "off"  — never.
    # The carried cache costs k*S*F*B*C f32 of HBM (updated slab-by-slab
    # on the fused path); when that exceeds ``hist_reuse_budget_mb`` the
    # engine falls back to "off" (engine.resolve_hist_reuse).
    hist_reuse: str = "auto"
    hist_reuse_budget_mb: int = 256   # cache budget gate for hist_reuse
    # Backend "auto" resolution (all three knobs below): pallas ONLY when
    # `jax.default_backend() == "tpu"`, the XLA oracle everywhere else.
    # Off-TPU the pallas kernels exist solely in `interpret=True`
    # emulation — a Python-level interpreter, not hardware — and the
    # measured CPU numbers in BENCH_kernels.json make the policy hard:
    # predict_pallas is ~65x slower than predict_xla (162983 vs 2513
    # us/call), level_hist_pallas ~1.3x slower than segment_sum, and
    # level_scores_pallas ~1.7x slower than the xla scorer. "auto" must
    # therefore NEVER resolve to an emulated kernel: the resolvers
    # (histograms.resolve_backend, gain.resolve_split_backend,
    # voting.resolve_predict_backend) key on the platform, never on
    # availability. Force `*_backend="pallas"` off-TPU only to exercise
    # the kernel code paths (that is what the parity tests do).
    #
    # T_GR backend: "pallas" = fused MXU one-hot-matmul kernel
    # (kernels/gain_ratio), "segment_sum" = XLA scatter vmap. See PERF.md.
    hist_backend: str = "auto"
    # T_NS backend: "pallas" = fused split-scan kernel (kernels/split_scan)
    # — on the single-host path it chains hist-kernel -> score-kernel per
    # feature slab so the [tc, S, F, B, C] histogram never reaches HBM;
    # "xla" = vectorized jnp argmax over the full histogram. See PERF.md.
    split_backend: str = "auto"
    # Prediction backend: "pallas" = fused traversal+voting kernel
    # (kernels/tree_traverse) — the depth walk runs in VMEM and the
    # Eq. 9/10 weighted vote accumulates across the tree grid axis, so
    # the [k, N, C] per-tree probability tensor never exists; "xla" =
    # route_to_leaves + weighted_vote over the full tensor. Honored by
    # voting.predict / predict_regression, PRFModel.predict and
    # serving/. See PERF.md.
    predict_backend: str = "auto"

    def __post_init__(self):
        # Bin ids are uint8 end to end — reject wrap-prone counts with a
        # typed error at config time, not as corrupted histograms later.
        from .binning import validate_n_bins

        validate_n_bins(self.n_bins)
        if self.bin_fit not in ("auto", "exact", "blocked"):
            raise ValueError(
                f"bin_fit must be 'auto', 'exact' or 'blocked', got {self.bin_fit!r}"
            )
        if self.hist_reuse not in ("auto", "on", "off"):
            raise ValueError(
                f"hist_reuse must be 'auto', 'on' or 'off', got {self.hist_reuse!r}"
            )

    def resolved_bin_fit(self) -> str:
        """Resolve bin_fit='auto': blocked iff the trainer streams blocks."""
        if self.bin_fit != "auto":
            return self.bin_fit
        return "blocked" if self.sample_block > 0 else "exact"

    def resolved_hist_reuse(self) -> str:
        """Resolve hist_reuse='auto': reuse is bitwise-exact only for
        integer classification counts, so auto enables it for
        classification and keeps regression (float channel sums) off.
        The shape-dependent cache budget gate is applied downstream
        (``engine.resolve_hist_reuse``)."""
        if self.hist_reuse != "auto":
            return self.hist_reuse
        return "off" if self.regression else "on"

    @property
    def frontier(self) -> int:
        f = self.max_frontier if self.max_frontier > 0 else 2 ** self.max_depth
        return min(f, 2 ** self.max_depth)

    @property
    def max_splits_per_level(self) -> int:
        return max(self.frontier // 2, 1)

    @property
    def max_nodes(self) -> int:
        # Each level allocates one band of at most 2*max_splits children.
        return 1 + 2 * self.max_splits_per_level * self.max_depth

    def resolved(self, n_features: int) -> "ForestConfig":
        """Fill data-dependent defaults (m = ceil(sqrt(M)), k_imp = ceil(sqrt(m)))."""
        import math

        m = self.n_selected if self.n_selected > 0 else max(1, int(math.ceil(math.sqrt(n_features))))
        m = min(m, n_features)
        k_imp = self.n_important if self.n_important > 0 else max(1, int(math.ceil(math.sqrt(m))))
        k_imp = min(k_imp, m)
        return dataclasses.replace(self, n_selected=m, n_important=k_imp)


@_pytree_dataclass
@dataclasses.dataclass
class Forest:
    """A trained PRF model — flat node-pool representation.

    Shapes (k = n_trees, P = max_nodes, C = n_classes):
      feature      [k, P] int32   split feature, -1 => leaf / unused
      threshold    [k, P] int32   go left iff bin <= threshold
      left_child   [k, P] int32   pool id of left child (right = left+1), -1 => leaf
      class_counts [k, P, C] f32  weighted class histogram at node creation
      value        [k, P] f32     regression value (weighted mean of y)
      tree_weight  [k] f32        w_i — OOB accuracy (Eq. 8) or 1.0
    """

    feature: jnp.ndarray
    threshold: jnp.ndarray
    left_child: jnp.ndarray
    class_counts: jnp.ndarray
    value: jnp.ndarray
    tree_weight: jnp.ndarray
    config: ForestConfig = static_field(default=None)

    @property
    def n_trees(self) -> int:
        return self.feature.shape[0]


@_pytree_dataclass
@dataclasses.dataclass
class GrowthState:
    """The growth engine's level-loop carry (core/engine.py).

    One value of this pytree fully describes a paused level-synchronous
    training run: ``core.engine.grow`` threads it through a
    ``lax.while_loop`` (early-exit scheduling), and the host-streaming
    driver (``core.distributed.grow_forest_streamed``) keeps the same fields
    across its per-block device calls. Registered as a pytree so it
    round-trips ``jax.jit`` boundaries (see tests/test_engine.py).
    """

    forest: Forest
    slot_node: jnp.ndarray     # [k, S] pool node id of each active frontier slot, -1 idle
    sample_slot: jnp.ndarray   # [k, N] frontier slot of each sample, -1 parked
    rng: jnp.ndarray           # PRNGKey (reserved for stochastic split policies)
    level: jnp.ndarray         # scalar int32 — next level to grow
    # Sibling-subtraction histogram cache (``config.hist_reuse``): the
    # previous level's post-combine per-slot histograms in rank-paired
    # row order plus the slot->row permutation and the next level's
    # parent/small-side tables (see ``engine.resolve_hist_reuse`` /
    # ``histograms.sibling_expand``). ``None`` when reuse is off — a
    # None leaf is an empty pytree, so off-mode states, jaxprs and
    # checkpoints are unchanged. As a pytree leaf the cache rides every
    # carry (``lax.while_loop``, jit boundaries, ``CheckpointManager``),
    # which is what keeps ``resume_from`` bit-identical with reuse on.
    hist_cache: Optional[dict] = None
