"""Single-host PRF training & prediction entry points (paper Alg. 4.2).

Training is one thin call into the unified task-DAG growth engine
(``core/engine.py``): ``grow_forest`` builds a ``LocalPlane`` (identity
collectives — the whole ``[N, F]`` block lives on one device) and runs
the engine's ``lax.while_loop`` level-step. The mesh-sharded trainer
(``core/distributed.py``) and the host-streaming out-of-core driver
(``core.distributed.grow_forest_streamed``) run the exact same level-step
over their own planes, so the growth logic exists once.

The T_GR/T_NS chunking machinery (``chunked_level_scores``,
``fused_level_scores``) and the shared node-pool helpers live in
``core/engine.py`` and are re-exported here for compatibility.

Prediction (``route_to_leaves`` + the fused traversal path) stays here.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from .engine import (  # noqa: F401  (re-exported: training internals)
    LocalPlane, _gather_feature_bins, _rank_splits, _safe_mean,
    chunked_level_scores, fused_level_scores, fused_reuse_level_scores,
    grow, grow_checkpointed, grow_with_leaves, init_forest, resolve_hist_reuse,
    reuse_level_task_group,
)
from .histograms import label_channels
from .tracing import scope
from .types import Forest, ForestConfig


def grow_forest(
    x_binned: jnp.ndarray,          # [N, F] uint8
    y: jnp.ndarray,                 # [N] int32 labels (float for regression)
    weights: jnp.ndarray,           # [k, N] in-bag multiplicities (DSI counts)
    config: ForestConfig,
    feature_mask: Optional[jnp.ndarray] = None,   # [k, F] bool (dim-reduction)
) -> Forest:
    """Train k trees level-synchronously. Pure function of its inputs.

    When ``config.weighted_voting`` asks for OOB weights, the forest
    keeps the leaf every row of ``x_binned`` reached in the growth loop
    (``grown_leaves``), so ``voting.oob_accuracy`` / ``oob_r2`` on these
    rows read it instead of walking the forest again. The record is not
    part of the pytree: ``dataclasses.replace`` and every JAX transform
    drop it. The leaves ride on the forest, not in the return value, so
    that growth and OOB scoring stay two calls of ``train_prf`` that
    can each be replaced on its own.
    """
    forest, leaves = _grow_forest_impl(x_binned, y, weights, config, feature_mask)
    return forest if leaves is None else with_leaves(forest, x_binned, leaves)


def with_leaves(forest: Forest, x_binned: jnp.ndarray, leaves: jnp.ndarray) -> Forest:
    """A copy of ``forest`` that records ``leaves`` ([k, N] int32) as the
    leaf of every row of ``x_binned`` (``grown_leaves``)."""
    out = dataclasses.replace(forest)
    out._leaves_of = (x_binned, leaves)
    return out


def grown_leaves(forest: Forest, x_binned: jnp.ndarray) -> Optional[jnp.ndarray]:
    """The leaves ``forest`` recorded for exactly this ``x_binned`` array
    (``with_leaves``), else None: ``route_to_leaves`` must walk."""
    rec = getattr(forest, "_leaves_of", None)
    return rec[1] if rec is not None and rec[0] is x_binned else None


def grow_forest_checkpointed(
    x_binned: jnp.ndarray,
    y: jnp.ndarray,
    weights: jnp.ndarray,
    config: ForestConfig,
    feature_mask: Optional[jnp.ndarray] = None,
    *,
    manager=None,
    resume_from: Optional[str] = None,
    on_level=None,
) -> Forest:
    """``grow_forest`` with per-level checkpointing / crash resume.

    A host-driven loop over the engine's jitted ``level_step`` (see
    ``engine.grow_checkpointed``): the forest is bit-identical to
    ``grow_forest``, and a run restored from any level-boundary
    checkpoint finishes with the same trees an uninterrupted run grows
    (tests/test_fault.py kills it at every boundary to pin this).
    """
    base = label_channels(y, config)
    return grow_checkpointed(
        x_binned, base, weights, config, LocalPlane(feature_mask),
        manager=manager, resume_from=resume_from, on_level=on_level,
    )


@partial(jax.jit, static_argnames=("config",))
def _grow_forest_impl(x_binned, y, weights, config, feature_mask):
    """The grow program: ``(forest, leaves)``, the ``[k, N]`` leaves of
    ``engine.grow_with_leaves`` when the forest will be OOB-weighted,
    else None (and the compiler drops the carry)."""
    base = label_channels(y, config)
    forest, leaves = grow_with_leaves(
        x_binned, base, weights, config, LocalPlane(feature_mask)
    )
    return forest, (leaves if config.weighted_voting else None)


# ---------------------------------------------------------------------------
# Prediction
# ---------------------------------------------------------------------------


@jax.jit
@scope("walk")
def route_to_leaves(forest: Forest, x_binned: jnp.ndarray) -> jnp.ndarray:
    """Leaf pool-id of every sample under every tree. Returns [k, N] int32."""
    k = forest.feature.shape[0]
    N = x_binned.shape[0]
    depth = forest.config.max_depth
    xb = x_binned.astype(jnp.int32)

    def step(node, _):
        f = jnp.take_along_axis(forest.feature, node, 1)               # [k, N]
        leaf = f < 0
        f_safe = jnp.where(leaf, 0, f)
        b = _gather_feature_bins(xb, f_safe)
        thr = jnp.take_along_axis(forest.threshold, node, 1)
        lc = jnp.take_along_axis(forest.left_child, node, 1)
        nxt = lc + (b > thr).astype(jnp.int32)
        return jnp.where(leaf, node, nxt), None

    node0 = jnp.zeros((k, N), jnp.int32)
    leaves, _ = jax.lax.scan(step, node0, None, length=depth)
    return leaves


def predict_proba_trees(forest: Forest, x_binned: jnp.ndarray) -> jnp.ndarray:
    """Per-tree class distributions h_i(x). Returns [k, N, C]."""
    leaves = route_to_leaves(forest, x_binned)
    counts = jnp.take_along_axis(forest.class_counts, leaves[..., None], axis=1)
    return counts / jnp.maximum(counts.sum(-1, keepdims=True), 1e-38)


def labels_at_leaves(forest: Forest, leaves: jnp.ndarray) -> jnp.ndarray:
    """Predicted class ``argmax_c h_i`` at each of ``leaves`` ([k, N]).

    The argmax is taken per pool node and then gathered at the leaves,
    which equals the argmax of ``predict_proba_trees`` without ever
    building the ``[k, N, C]`` tensor (on a TPU its small class axis
    pads to a full lane tile, 64x at C=2).
    """
    counts = forest.class_counts
    probs = counts / jnp.maximum(counts.sum(-1, keepdims=True), 1e-38)
    return jnp.take_along_axis(
        jnp.argmax(probs, axis=-1).astype(jnp.int32), leaves, axis=1
    )


def predict_value_trees(forest: Forest, x_binned: jnp.ndarray) -> jnp.ndarray:
    """Per-tree regression outputs h_i(x). Returns [k, N]."""
    leaves = route_to_leaves(forest, x_binned)
    return jnp.take_along_axis(forest.value, leaves, axis=1)


@jax.jit
def fused_vote_scores(
    forest: Forest,
    x_binned: jnp.ndarray,      # [N, F] uint8
    payload: jnp.ndarray,       # [k, P, C] weighted per-node vote vectors
) -> jnp.ndarray:
    """Weighted-vote scores via the fused traversal kernel. Returns [N, C].

    The predict-side analogue of ``fused_level_scores``: trees are
    processed in ``tree_chunk`` groups, each chunk's ``pallas_call``
    walking the depth loop in VMEM and folding its votes into the
    ``[N, C]`` score carry threaded through the chunk loop — the
    ``[k, N, C]`` per-tree tensor of the xla path
    (``predict_proba_trees`` -> ``weighted_vote``) never exists
    (jaxpr-verified by tests/test_predict_backends.py). Chunking is
    exact (each tree contributes an exact payload row), so any chunk
    size — including a non-divisible final remainder — gives the same
    scores.
    """
    from ..kernels import default_interpret
    from ..kernels.tree_traverse.kernel import traverse_block

    k = forest.feature.shape[0]
    config = forest.config
    tc = config.tree_chunk if config.tree_chunk > 0 else k
    tc = min(tc, k)
    interpret = default_interpret()

    carry = None
    for c0 in range(0, k, tc):
        c1 = min(c0 + tc, k)
        carry = traverse_block(
            x_binned,
            forest.feature[c0:c1],
            forest.threshold[c0:c1],
            forest.left_child[c0:c1],
            payload[c0:c1],
            carry,
            depth=config.max_depth,
            interpret=interpret,
        )
    return carry
