"""OOB-weighted voting (paper §3.3, Eq. 8-10).

After training, each tree h_i is evaluated on its own Out-Of-Bag set
OOB_i; the classification accuracy CA_i (Eq. 8) becomes the tree's voting
weight w_i. Prediction then takes the weighted majority (Eq. 10) or the
weighted regression average (Eq. 9).

Prediction has two backends, selected by ``ForestConfig.predict_backend``
and dispatched by ``predict`` / ``predict_regression`` / the score-level
``predict_scores``:

* ``"xla"``    — ``route_to_leaves`` + ``weighted_vote`` over the full
  ``[k, N, C]`` per-tree probability tensor (portable oracle);
* ``"pallas"`` — the fused traversal+voting kernel
  (``kernels/tree_traverse``): the depth walk runs in VMEM and the
  weighted vote accumulates across the tree grid axis, so only the
  ``[N, C]`` scores ever exist;
* ``"auto"``   — ``pallas`` on TPU, else ``xla``.

Both backends vote with the same per-leaf payloads (``leaf_vote_payload``
/ ``leaf_value_payload``: tree weight folded into the per-node vote
vector), so predicted labels are identical across backends.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .forest import (
    fused_vote_scores, grown_leaves, labels_at_leaves, predict_proba_trees,
    predict_value_trees, route_to_leaves,
)
from .tracing import scope
from .types import Forest

PREDICT_BACKENDS = ("auto", "pallas", "xla")


def resolve_predict_backend(backend: str) -> str:
    """'auto' -> 'pallas' on TPU, 'xla' elsewhere."""
    if backend not in PREDICT_BACKENDS:
        raise ValueError(
            f"predict_backend={backend!r} not in {PREDICT_BACKENDS}"
        )
    if backend == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    return backend


def _leaves(forest: Forest, x_binned: jnp.ndarray) -> jnp.ndarray:
    """Leaf of every row of ``x_binned``: the growth loop's own, when
    ``forest`` recorded them for these rows (``forest.grow_forest``),
    else a walk of the forest."""
    leaves = grown_leaves(forest, x_binned)
    return route_to_leaves(forest, x_binned) if leaves is None else leaves


def oob_accuracy(
    forest: Forest, x_binned: jnp.ndarray, y: jnp.ndarray, weights: jnp.ndarray
) -> jnp.ndarray:
    """Eq. (8): CA_i = #correct / (#correct + #error) over OOB_i
    (``oob_accuracy_at_leaves`` at the leaves of ``x_binned``).

    Args:
      weights: [k, N] in-bag multiplicities (0 => sample is OOB for tree).
    Returns: [k] float32 accuracies; a tree with an empty OOB set gets
    the neutral prior 0.5.
    """
    return oob_accuracy_at_leaves(forest, _leaves(forest, x_binned), y, weights)


@scope("oob")
def _oob_counts(forest: Forest, leaves, y, w):
    """Eq. (8)'s counts at the leaves: (#correct, #OOB) per tree."""
    pred = labels_at_leaves(forest, leaves)                # [k, N]
    oob = (w == 0.0).astype(jnp.float32)                   # [k, N]
    correct = jnp.sum(oob * (pred == y[None]).astype(jnp.float32), axis=1)
    return correct, jnp.sum(oob, axis=1)


def _accuracy(correct, total):
    """A tree whose OOB set is empty (every sample in-bag — possible
    under the DSI bootstrap) has no evidence either way and gets the
    **neutral prior 0.5**, never a degenerate 0/0."""
    return jnp.where(total > 0, correct / jnp.maximum(total, 1.0), 0.5)


@jax.jit
def oob_accuracy_at_leaves(
    forest: Forest, leaves: jnp.ndarray, y: jnp.ndarray, weights: jnp.ndarray
) -> jnp.ndarray:
    """Eq. (8) with each sample's leaf given: ``leaves`` [k, N] int32 pool
    ids, ``weights`` [k, N] in-bag multiplicities (0 => OOB). [k] f32."""
    return _accuracy(*_oob_counts(forest, leaves, y, weights))


def oob_r2(forest, x_binned, y, weights):
    """Regression analogue of Eq. (8): per-tree OOB R^2 clipped to [0, 1].

    Degenerate OOB sets get the same **neutral prior 0.5** as
    ``oob_accuracy`` — both when the OOB set is empty (previously the
    0/eps arithmetic silently produced a confident 1.0) and when its
    target variance is zero (R^2 undefined; the clip used to hide the
    garbage ratio). Only a tree with real OOB evidence earns a
    non-neutral weight.

    The sample reduction runs on HOST in float64 over per-sample f32
    moment terms (``_r2_leaf_terms`` — the same jitted kernel the
    streamed path folds per block), then one final float32 cast. That
    makes ``oob_r2`` and ``oob_r2_streamed`` **bit-identical**: the
    per-sample terms are batch-shape independent, and the float64
    accumulations (one-shot pairwise here, Neumaier-compensated across
    blocks there) agree to well under a float32 ulp before the cast.
    Like ``oob_accuracy``, it reads the growth loop's leaves when the
    forest recorded them for ``x_binned``.
    """
    y32 = jnp.asarray(y, jnp.float32)
    w32 = jnp.asarray(weights, jnp.float32)
    sum_y, total = _r2_mean_stats(y32, w32)
    mean = sum_y / jnp.maximum(total, 1.0)
    err_t, var_t = _r2_leaf_terms(forest, _leaves(forest, x_binned), y32, w32, mean)
    return _r2_finalize(
        np.asarray(err_t, np.float64).sum(axis=1),
        np.asarray(var_t, np.float64).sum(axis=1),
        np.asarray(total, np.float64),
    )


def weighted_vote(
    probs: jnp.ndarray, tree_weight: jnp.ndarray, *, soft: bool = False
) -> jnp.ndarray:
    """Eq. (10): H_c(X) = Majority_i [ w_i x h_i(x) ].

    Args:
      probs: [k, N, C] per-tree class distributions.
      tree_weight: [k] w_i = CA_i (or ones for the unweighted baseline).
      soft: weight the full distribution instead of the argmax vote
            (a strictly-stronger variant; the paper's Eq. 10 is hard).
    Returns: scores [N, C]; argmax is the predicted class.
    """
    w = tree_weight[:, None, None]
    if soft:
        return jnp.sum(w * probs, axis=0)
    votes = jax.nn.one_hot(jnp.argmax(probs, -1), probs.shape[-1], dtype=probs.dtype)
    return jnp.sum(w * votes, axis=0)


def weighted_regression(
    values: jnp.ndarray, tree_weight: jnp.ndarray, *, faithful_eq9: bool = False
) -> jnp.ndarray:
    """Eq. (9): H_r(X) = (1/k) sum_i w_i * h_i(x).

    The literal Eq. (9) divides by k, which biases the magnitude whenever
    sum(w) != k; the default normalizes by sum(w) (the standard weighted
    mean). ``faithful_eq9=True`` reproduces the paper exactly.
    """
    w = tree_weight[:, None]
    if faithful_eq9:
        return jnp.mean(w * values, axis=0)
    return jnp.sum(w * values, axis=0) / jnp.maximum(tree_weight.sum(), 1e-38)


# ---------------------------------------------------------------------------
# Streamed OOB + prediction — the sample-block carriers of the data plane
# ---------------------------------------------------------------------------


def _block_feeder(x_binned, sample_block, prefetch, *, what,
                  n_y=None, n_w=None):
    """BlockFeeder over a validated block list (``pipeline.stream_blocks``:
    explicit sequences pass through — device arrays included — array
    sources require ``sample_block > 0``, and blocks must cover the
    caller's label/weight lengths when given)."""
    from ..data.pipeline import BlockFeeder, stream_blocks

    return BlockFeeder(
        stream_blocks(x_binned, sample_block, what=what, n_y=n_y, n_w=n_w),
        prefetch=prefetch,
    )


@jax.jit
def _oob_block_counts(forest: Forest, xb_b, y_b, w_b):
    """One block's contribution to Eq. (8): (#correct, #OOB) per tree."""
    return _oob_counts(forest, route_to_leaves(forest, xb_b), y_b, w_b)


def oob_accuracy_streamed(
    forest: Forest, x_binned, y, weights, *,
    sample_block: int | None = None, prefetch: int = 2,
) -> jnp.ndarray:
    """Eq. (8) accumulated over sample blocks — the full binned matrix is
    never device-resident. ``#correct`` and ``#OOB`` are sums of 0/1
    floats (exact f32 integers), so the blocked accumulation is
    **bit-identical** to the resident ``oob_accuracy``."""
    y_np = np.asarray(y)
    w_np = np.asarray(weights, dtype=np.float32)
    feeder = _block_feeder(
        x_binned, sample_block, prefetch, what="oob_accuracy_streamed",
        n_y=y_np.shape[0], n_w=w_np.shape[1],
    )
    k = w_np.shape[0]
    correct = jnp.zeros((k,), jnp.float32)
    total = jnp.zeros((k,), jnp.float32)
    o = 0
    with feeder:
        for xb_b in feeder.sweep():
            n = xb_b.shape[0]
            c, t = _oob_block_counts(
                forest, xb_b, feeder.pin(y_np[o:o + n]),
                feeder.pin(w_np[:, o:o + n]),
            )
            correct, total = correct + c, total + t
            o += n
    return _accuracy(correct, total)


@jax.jit
@scope("oob")
def _r2_mean_stats(y, w):
    """The OOB mean's sufficient statistics — needs y/weights only, so
    it runs on the full [k, N] arrays exactly like the resident path
    (same one-shot jnp sums, no feature block ever touched)."""
    oob = (w == 0.0).astype(jnp.float32)
    return jnp.sum(oob * y[None], axis=1), oob.sum(1)


@jax.jit
@scope("oob")
def _r2_leaf_terms(forest: Forest, leaves, y_b, w_b, mean):
    """Per-sample OOB squared-error / variance terms at the samples'
    leaves, [k, Nb] each. The moment arithmetic is per-sample
    elementwise, so each term is bit-identical whether the block is the
    whole dataset or one slice of it — the same batch-shape independence
    the streamed predict parity rests on. The sample reduction
    deliberately does NOT happen on device: both ``oob_r2`` paths
    reduce the terms on host in float64."""
    vals = jnp.take_along_axis(forest.value, leaves, axis=1)   # [k, Nb]
    oob = (w_b == 0.0).astype(jnp.float32)
    err_t = oob * (vals - y_b[None]) ** 2
    var_t = oob * (y_b[None] - mean[:, None]) ** 2
    return err_t, var_t


@jax.jit
def _r2_block_terms(forest: Forest, xb_b, y_b, w_b, mean):
    """``_r2_leaf_terms`` at the leaves of one block's rows."""
    return _r2_leaf_terms(forest, route_to_leaves(forest, xb_b), y_b, w_b, mean)


def _neumaier_add(s: np.ndarray, c: np.ndarray, x: np.ndarray) -> None:
    """One Neumaier-compensated accumulation step, in place: ``s += x``
    with the rounding error banked in the running compensation ``c``
    (all float64 [k]). The true sum is ``s + c``."""
    t = s + x
    c += np.where(np.abs(s) >= np.abs(x), (s - t) + x, (x - t) + s)
    s[:] = t


def _r2_finalize(err_sum, var_sum, total) -> jnp.ndarray:
    """R^2 from the float64 moment sums (np.float64 [k] each): the
    whole formula evaluates in float64, then ONE cast to float32 — the
    only rounding either oob_r2 path performs after the per-sample
    terms. Neutral prior 0.5 for degenerate OOB sets."""
    n = np.maximum(total, 1.0)
    err = err_sum / n
    var = var_sum / n
    r2 = np.clip(1.0 - err / np.maximum(var, 1e-300), 0.0, 1.0)
    out = np.where((total > 0) & (var_sum > 0), r2, 0.5)
    return jnp.asarray(out.astype(np.float32))


def oob_r2_streamed(
    forest: Forest, x_binned, y, weights, *,
    sample_block: int | None = None, prefetch: int = 2,
) -> jnp.ndarray:
    """Blocked ``oob_r2``: ONE sweep over the feature blocks. The OOB
    mean needs only ``y``/``weights`` (computed with the resident
    path's one-shot sums — no block feed), so only the moment pass
    streams the ``[Nb, F]`` blocks. Per-block float64 partial sums are
    folded with Neumaier compensation, so the result is
    **bit-identical** to the resident ``oob_r2`` (see its docstring;
    tests/test_engine.py pins the equality)."""
    y_np = np.asarray(y, dtype=np.float32)
    w_np = np.asarray(weights, dtype=np.float32)
    feeder = _block_feeder(
        x_binned, sample_block, prefetch, what="oob_r2_streamed",
        n_y=y_np.shape[0], n_w=w_np.shape[1],
    )
    sum_y, total = _r2_mean_stats(jnp.asarray(y_np), jnp.asarray(w_np))
    mean = sum_y / jnp.maximum(total, 1.0)

    k = w_np.shape[0]
    err_sum, err_c = np.zeros(k, np.float64), np.zeros(k, np.float64)
    var_sum, var_c = np.zeros(k, np.float64), np.zeros(k, np.float64)
    o = 0
    with feeder:
        for xb_b in feeder.sweep():
            nb = xb_b.shape[0]
            err_t, var_t = _r2_block_terms(
                forest, xb_b, feeder.pin(y_np[o:o + nb]),
                feeder.pin(w_np[:, o:o + nb]), mean,
            )
            _neumaier_add(err_sum, err_c, np.asarray(err_t, np.float64).sum(1))
            _neumaier_add(var_sum, var_c, np.asarray(var_t, np.float64).sum(1))
            o += nb
    return _r2_finalize(
        err_sum + err_c, var_sum + var_c, np.asarray(total, np.float64)
    )


def predict_scores_streamed(
    forest: Forest, x_binned, *, sample_block: int | None = None,
    backend: str | None = None, prefetch: int = 2,
) -> jnp.ndarray:
    """``predict_scores`` over sample blocks. Scores are per-sample, so
    the blocked path is bit-identical to the resident call; only the
    [N, C] score matrix (never [N, F]) is materialized."""
    feeder = _block_feeder(
        x_binned, sample_block, prefetch, what="predict_scores_streamed"
    )
    with feeder:
        return jnp.concatenate([
            predict_scores(forest, xb_b, backend=backend)
            for xb_b in feeder.sweep()
        ])


def predict_streamed(
    forest: Forest, x_binned, *, sample_block: int | None = None,
    backend: str | None = None, prefetch: int = 2,
) -> jnp.ndarray:
    """Streamed classification labels [N] (bit-identical to ``predict``)."""
    return jnp.argmax(
        predict_scores_streamed(
            forest, x_binned, sample_block=sample_block, backend=backend,
            prefetch=prefetch,
        ),
        axis=-1,
    )


def predict_regression_streamed(
    forest: Forest, x_binned, *, sample_block: int | None = None,
    backend: str | None = None, prefetch: int = 2,
) -> jnp.ndarray:
    """Streamed regression predictions [N] (per-sample, so bit-identical
    to ``predict_regression``)."""
    feeder = _block_feeder(
        x_binned, sample_block, prefetch, what="predict_regression_streamed"
    )
    with feeder:
        num = jnp.concatenate([
            predict_regression_scores(forest, xb_b, backend=backend)
            for xb_b in feeder.sweep()
        ])
    return num / jnp.maximum(_vote_weights(forest).sum(), 1e-38)


# ---------------------------------------------------------------------------
# Leaf payloads — the fused backend's vote vectors (weight folded in)
# ---------------------------------------------------------------------------


def leaf_vote_payload(
    forest: Forest, tree_weight: jnp.ndarray, *, soft: bool = False
) -> jnp.ndarray:
    """Per-(tree, node) classification vote vectors, weight folded in.

    ``payload[t, p] = w_t * onehot(argmax_c probs[t, p])`` (hard,
    Eq. 10) or ``w_t * probs[t, p]`` (soft), where ``probs`` are the
    node's normalized class counts — exactly what the xla path computes
    per *leaf*, precomputed for every pool node so the fused kernel is
    a pure traversal + payload gather. [k, P, C] float32.
    """
    counts = forest.class_counts
    total = counts.sum(-1, keepdims=True)
    # Zero-mass pool slots (the scatter pad, never-allocated bands) vote
    # zero. The unguarded 0 / maximum(0, 1e-38) is NaN — 1e-38 is a
    # subnormal f32 that XLA flushes to zero — and the fused kernel's
    # one-hot matmul reads EVERY pool row (0 * NaN poisons the scores);
    # the xla path only gathers reachable leaves, where total > 0 makes
    # the two normalizations identical.
    probs = jnp.where(total > 0, counts / jnp.maximum(total, 1e-38), 0.0)
    if soft:
        vote = probs
    else:
        vote = jnp.where(
            total > 0,
            jax.nn.one_hot(
                jnp.argmax(probs, -1), probs.shape[-1], dtype=jnp.float32
            ),
            0.0,
        )
    return tree_weight[:, None, None] * vote


def leaf_value_payload(forest: Forest, tree_weight: jnp.ndarray) -> jnp.ndarray:
    """Per-(tree, node) weighted regression values, [k, P, 1] float32.

    ``payload[t, p, 0] = w_t * value[t, p]`` — the Eq. (9) numerator;
    the ``/ sum_i w_i`` normalization happens on the [N] result.
    Zero-mass pool slots get a zero payload (see ``leaf_vote_payload``:
    the fused kernel requires finite payloads at every pool row).
    """
    mass = forest.class_counts[..., 0]          # regression count channel
    value = jnp.where(mass > 0, forest.value, 0.0)
    return (tree_weight[:, None] * value)[..., None]


# ---------------------------------------------------------------------------
# Backend-dispatched prediction
# ---------------------------------------------------------------------------


def _vote_weights(forest: Forest) -> jnp.ndarray:
    return (
        forest.tree_weight
        if forest.config.weighted_voting
        else jnp.ones_like(forest.tree_weight)
    )


def build_payload(forest: Forest) -> jnp.ndarray:
    """The forest's vote payload under its own config — the ONE place
    that maps (regression, soft_voting, weighted_voting) to a payload
    (used by the serving layer's direct and tree-sharded paths)."""
    w = _vote_weights(forest)
    if forest.config.regression:
        return leaf_value_payload(forest, w)
    return leaf_vote_payload(forest, w, soft=forest.config.soft_voting)


@jax.jit
def _fused_class_scores(forest: Forest, x_binned: jnp.ndarray) -> jnp.ndarray:
    """jit'd pallas-backend scores: payload construction is traced into
    the same compiled program as the traversal, so a predict call does
    no eager per-request O(k*P*C) work."""
    payload = leaf_vote_payload(
        forest, _vote_weights(forest), soft=forest.config.soft_voting
    )
    return fused_vote_scores(forest, x_binned, payload)


@jax.jit
def _fused_value_scores(forest: Forest, x_binned: jnp.ndarray) -> jnp.ndarray:
    payload = leaf_value_payload(forest, _vote_weights(forest))
    return fused_vote_scores(forest, x_binned, payload)[:, 0]


def predict_scores(
    forest: Forest, x_binned: jnp.ndarray, *, backend: str | None = None
) -> jnp.ndarray:
    """Weighted-vote class scores [N, C] (argmax = predicted label).

    Dispatches on ``backend`` (default ``forest.config.predict_backend``):
    the fused pallas path never materializes the ``[k, N, C]`` per-tree
    tensor; the xla path is the portable oracle.
    """
    backend = resolve_predict_backend(
        backend if backend is not None else forest.config.predict_backend
    )
    if backend == "pallas":
        return _fused_class_scores(forest, x_binned)
    probs = predict_proba_trees(forest, x_binned)
    return weighted_vote(probs, _vote_weights(forest), soft=forest.config.soft_voting)


def predict_regression_scores(
    forest: Forest, x_binned: jnp.ndarray, *, backend: str | None = None
) -> jnp.ndarray:
    """Unnormalized Eq. (9) numerator ``sum_i w_i h_i(x)`` as [N]."""
    backend = resolve_predict_backend(
        backend if backend is not None else forest.config.predict_backend
    )
    if backend == "pallas":
        return _fused_value_scores(forest, x_binned)
    vals = predict_value_trees(forest, x_binned)
    return jnp.sum(_vote_weights(forest)[:, None] * vals, axis=0)


def predict(
    forest: Forest, x_binned: jnp.ndarray, *, backend: str | None = None
) -> jnp.ndarray:
    """Full PRF prediction (classification): weighted majority class [N]."""
    return jnp.argmax(predict_scores(forest, x_binned, backend=backend), axis=-1)


def predict_regression(
    forest: Forest, x_binned: jnp.ndarray, *, backend: str | None = None
) -> jnp.ndarray:
    """Full PRF regression prediction: weighted mean of h_i(x), [N]."""
    num = predict_regression_scores(forest, x_binned, backend=backend)
    return num / jnp.maximum(_vote_weights(forest).sum(), 1e-38)
