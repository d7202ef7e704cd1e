"""Pallas TPU kernel: fused tree traversal + weighted voting (paper §3.3).

Training (PR 1-2) is kernel-fused end to end, but prediction still ran
as an unrolled per-depth gather loop (``core/forest.route_to_leaves``)
that materialized the full ``[k, N, C]`` per-tree probability tensor in
HBM before Eq. (9)/(10) voting — for serving shapes that tensor is the
dominant memory traffic, and none of it survives the vote. This kernel
closes the prediction loop the same way ``kernels/split_scan`` closed
T_NS: the level-synchronous depth walk runs entirely in VMEM (the
forest's ``feature/threshold/left_child`` rows and the per-node vote
payload resident per tree-block, sample bins streamed in N-blocks), and
the weighted vote accumulates in-register across the tree grid axis as
a resumable carry. Only the ``[N, C]`` scores ever leave the kernel —
the ``[k, N, C]`` tensor never exists (jaxpr-verified by
``tests/test_predict_backends.py``).

Hard vs soft voting and classification vs regression are unified by the
**payload** input: per-(tree, node) vote vectors with the tree weight
``w_i`` already folded in —

    hard (Eq. 10):   payload[t, p] = w_t * onehot(argmax_c counts[t, p])
    soft:            payload[t, p] = w_t * counts[t, p] / sum_c counts
    regression (Eq. 9, C=1): payload[t, p, 0] = w_t * value[t, p]

so the kernel is a pure traversal + payload-accumulate; the Eq. (9)
normalization (``/ sum_i w_i`` or ``/ k``) happens on the tiny [N]
result outside. Payload construction lives in ``core/voting.py``.

Grid: ``(N_blocks, k)`` with the tree axis innermost (sequential), so
each sample block's score tile stays resident in VMEM while trees
stream through — the same reduction-grid pattern as the histogram
kernel. TPUs have no fast gather, so the per-depth node lookups are
one-hot select-reduces over the node pool and the final leaf-payload
read is a one-hot matmul on the MXU at ``Precision.HIGHEST`` (exact:
all other summands are literal zeros). The per-tree node rows are fed
as ``[1, P]`` blocks and the payload and scores with the class axis
leading (``[C, P]``, ``[C, N]``), so no block has a unit or a
class-sized lane dimension. The carry is resumable: callers seed the
score tile from a previous call's output (``core/forest.
fused_vote_scores`` chains tree chunks; ``serving/prf_service.py``
feeds each shard's partial votes into one ``psum``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..gain_ratio.kernel import _HIGHEST, _VMEM_BUDGET, _round_up, _tile_words


def traverse_vmem_bytes(n_blk: int, P: int, F: int, C: int) -> int:
    """Estimated VMEM of one traversal grid step: the double-buffered
    bins ``[n_blk, F]``, node rows ``3 x [1, P]``, payload ``[C, P]``,
    carry and score ``[C, n_blk]`` blocks, plus what the compiler's
    reported sizes show it keeps of the depth walk: eight per-sample
    ``[n_blk, 1]`` columns (node, feature, threshold, child, bin, ...;
    one 128-lane tile row per sample each) and one 128-row strip of the
    ``[n_blk, P]`` node one-hot. The one-hot is never held whole."""
    Pp = _round_up(P, 8)
    blocks = (
        _tile_words(n_blk, F) + 3 * _tile_words(1, Pp) + _tile_words(C, Pp)
        + 2 * _tile_words(C, n_blk)
    )
    temps = 8 * _tile_words(n_blk, 1) + _tile_words(128, Pp)
    return 4 * (2 * blocks + temps)


def choose_traverse_block(
    P: int, F: int, C: int, *,
    n_blk: int | None = None, vmem_budget: int = _VMEM_BUDGET,
) -> int:
    """Sample-block width for the traversal kernel: a multiple of 128
    (the lane axis of the score block), halved from 512 while
    ``traverse_vmem_bytes`` exceeds the shared budget."""
    if n_blk is None:
        n_blk = 512
        while n_blk > 128 and traverse_vmem_bytes(n_blk, P, F, C) > vmem_budget:
            n_blk //= 2
    return _round_up(n_blk, 128)


def _traverse_kernel(
    xb_ref, feat_ref, thr_ref, left_ref, payload_ref, s0_ref, out_ref,
    *, depth: int,
):
    """One (sample-block, tree) grid step: walk the tree, add its vote."""
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _seed_from_carry():
        out_ref[...] = s0_ref[...]

    xb = xb_ref[...]                                    # [n_blk, Fp] i32
    feat = feat_ref[0]                                  # [Pp] i32
    thr = thr_ref[0]
    left = left_ref[0]
    n_blk, Fp = xb.shape
    Pp = feat.shape[0]
    pcol = jax.lax.broadcasted_iota(jnp.int32, (n_blk, Pp), 1)
    fcol = jax.lax.broadcasted_iota(jnp.int32, (n_blk, Fp), 1)

    def step(_, node):
        # Node-pool gathers as one-hot select-reduces (no TPU gather);
        # exact — every non-selected summand is a literal zero.
        onehot = pcol == node[:, None]                  # [n_blk, Pp]
        f = jnp.sum(jnp.where(onehot, feat[None, :], 0), axis=1)
        th = jnp.sum(jnp.where(onehot, thr[None, :], 0), axis=1)
        lc = jnp.sum(jnp.where(onehot, left[None, :], 0), axis=1)
        leaf = f < 0
        f_safe = jnp.where(leaf, 0, f)
        b = jnp.sum(jnp.where(fcol == f_safe[:, None], xb, 0), axis=1)
        nxt = lc + (b > th).astype(jnp.int32)
        return jnp.where(leaf, node, nxt)

    node = jax.lax.fori_loop(
        0, depth, step, jnp.zeros((n_blk,), jnp.int32)
    )

    # Leaf payload read as a one-hot matmul on the MXU (exact).
    onehot = (pcol == node[:, None]).astype(jnp.float32)
    out_ref[...] += jax.lax.dot_general(
        payload_ref[...], onehot,
        dimension_numbers=(((1,), (1,)), ((), ())),     # payload @ onehot^T
        precision=_HIGHEST,
        preferred_element_type=jnp.float32,
    )                                                   # [C, n_blk]


def traverse_block(
    x_binned: jnp.ndarray,      # [N, F] int bins
    feature: jnp.ndarray,       # [tc, P] i32, -1 = leaf
    threshold: jnp.ndarray,     # [tc, P] i32
    left_child: jnp.ndarray,    # [tc, P] i32
    payload: jnp.ndarray,       # [tc, P, C] f32 weighted vote vectors
    carry: jnp.ndarray | None,  # [N, C] f32 running scores (None = zeros)
    *,
    depth: int,
    n_blk: int | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Fold one tree chunk's weighted votes into the running scores.

    Returns the updated ``[N, C]`` scores. Resumable: pass the result
    back as ``carry`` for the next chunk (or psum partial scores across
    tree shards) — chunked accumulation is exact because each tree's
    contribution is an exact payload row.
    """
    N, F = x_binned.shape
    tc, P = feature.shape
    C = payload.shape[-1]
    n_blk = choose_traverse_block(P, F, C, n_blk=n_blk)
    n_blk = min(n_blk, _round_up(max(N, 1), 128))

    Np, Fp, Pp = _round_up(N, n_blk), _round_up(F, 8), _round_up(P, 8)
    # Padded samples traverse the tree like real ones but are sliced off
    # the output; padded feature columns are never addressed (real
    # feature ids < F). Padded pool slots are leaves with zero payload,
    # unreachable anyway (traversal starts at the root, slot 0).
    xb = jnp.pad(x_binned.astype(jnp.int32), ((0, Np - N), (0, Fp - F)))
    node_row = lambda a, fill: jnp.pad(
        a.astype(jnp.int32), ((0, 0), (0, Pp - P)), constant_values=fill
    )[:, None, :]                                                # [tc, 1, Pp]
    payload_t = jnp.transpose(
        jnp.pad(payload.astype(jnp.float32), ((0, 0), (0, Pp - P), (0, 0))),
        (0, 2, 1),
    )                                                            # [tc, C, Pp]
    if carry is None:
        carry = jnp.zeros((N, C), jnp.float32)
    carry_t = jnp.pad(carry.astype(jnp.float32), ((0, Np - N), (0, 0))).T

    row_spec = pl.BlockSpec((None, 1, Pp), lambda n, t: (t, 0, 0))
    score_spec = pl.BlockSpec((C, n_blk), lambda n, t: (0, n))
    out = pl.pallas_call(
        functools.partial(_traverse_kernel, depth=depth),
        grid=(Np // n_blk, tc),
        in_specs=[
            pl.BlockSpec((n_blk, Fp), lambda n, t: (n, 0)),      # bins
            row_spec,                                            # feature
            row_spec,                                            # threshold
            row_spec,                                            # left_child
            pl.BlockSpec((None, C, Pp), lambda n, t: (t, 0, 0)), # payload^T
            score_spec,                                          # carry^T
        ],
        out_specs=score_spec,
        out_shape=jax.ShapeDtypeStruct((C, Np), jnp.float32),
        interpret=interpret,
    )(
        xb, node_row(feature, -1), node_row(threshold, 0),
        node_row(left_child, 0), payload_t, carry_t,
    )
    return out[:, :N].T
