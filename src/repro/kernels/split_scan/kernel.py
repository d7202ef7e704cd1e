"""Pallas TPU kernel: fused T_NS split scoring (paper §4.2.1, Eq. 2-6).

The histogram kernel (``kernels/gain_ratio``) writes the ``[tc, S, F,
B, C]`` level histogram; for training shapes that tensor is orders of
magnitude larger than the ``O(k*S)`` split descriptors that survive the
level. This kernel consumes histogram tiles in VMEM, computes the bin
prefix sums, the Eq. (2)-(6) gain ratios (or the ``variance_gains``
regression analogue) and the dim-reduction feature mask in-register,
and folds the T_NS argmax into the grid loop as a running ``(best_gr,
best_f, best_thr, left/right_counts)`` accumulator. Only the per-(tree,
slot) winners are ever written back.

Layout: inside the kernel a feature's histogram is ``[C, B, S]`` — the
frontier slots on the lane axis, the bins on sublanes, the classes
leading — so no block has a class-sized lane dimension. The bin prefix
sum is one ``[B, B]`` lower-triangular 0/1 matmul per class at
``Precision.HIGHEST``: exact for integer counts below 2**24, which is
what DSI-weighted classification histograms hold.

Grid: ``(tc, F_blocks)`` with the feature axis innermost (sequential),
so each tree's [S]-shaped accumulator stays resident in VMEM while
feature blocks stream through; within a block the features are folded
one at a time. The carry is *resumable*: callers pass the previous best
as inputs (seeded into the output at the first feature block) plus a
global feature offset, which is how ``core/engine.fused_level_scores``
chains histogram-kernel -> score-kernel per feature slab without ever
materializing the full histogram, and how ``core/distributed.py``
scores each shard's feature slice post-combine.

Numerics are shared with the XLA backend (``core/gain.py``'s
``*_from_cumsum`` scorers, evaluated with the class axis leading) and
carry updates are strictly-greater, so first-occurrence argmax
semantics match exactly: winners and child counts are bit-identical in
interpret mode (tests/test_split_backends.py). On a TPU, Mosaic's
log/division may round differently from XLA's, so two *near-tied*
candidate splits could flip order against the xla backend; the forests
stay valid either way.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ...core.gain import (
    SplitScores, split_gain_ratios_from_cumsum, variance_gains_from_cumsum,
)
from .. import default_interpret
from ..gain_ratio.kernel import _HIGHEST, _VMEM_BUDGET, _round_up, _tile_words


def score_vmem_bytes(f_blk: int, S: int, B: int, C: int) -> int:
    """Estimated VMEM of one split-scan grid step: the double-buffered
    ``[f_blk, C, B, S]`` histogram block, mask block and carry blocks
    (in and out), plus one feature's temporaries (prefix sums, left and
    right counts, entropy terms and the HIGHEST operand splits)."""
    carry = (3 + 2 * C) * _tile_words(1, S)
    blocks = f_blk * C * _tile_words(B, S) + f_blk * _tile_words(1, 1) + 2 * carry
    temps = 12 * C * _tile_words(B, S) + 3 * _tile_words(B, B)
    return 4 * (2 * blocks + temps)


def choose_score_block(
    S: int, B: int, C: int, F: int, *,
    f_blk: int | None = None, vmem_budget: int = _VMEM_BUDGET,
) -> int:
    """Feature-block width for the score kernel: a multiple of 8, halved
    from 32 while ``score_vmem_bytes`` exceeds the shared budget, never
    padding past one block of the real problem."""
    if f_blk is None:
        f_blk = 32
        while f_blk > 8 and score_vmem_bytes(f_blk, S, B, C) > vmem_budget:
            f_blk //= 2
    return min(f_blk, _round_up(max(F, 1), 8))


def init_carry(tc: int, S: int, C: int) -> tuple:
    """Neutral running-best carry: no winner yet (feature = -1).

    The kernel force-accepts the first feature's argmax while
    ``feature < 0``, which reproduces the XLA oracle's all-invalid
    semantics (gain -inf -> feature 0, threshold 0, counts of that
    split) without a special case.
    """
    return (
        jnp.full((tc, S), -jnp.inf, jnp.float32),   # best gain ratio
        jnp.full((tc, S), -1, jnp.int32),           # best feature (global id)
        jnp.zeros((tc, S), jnp.int32),              # best threshold
        jnp.zeros((tc, S, C), jnp.float32),         # left child counts
        jnp.zeros((tc, S, C), jnp.float32),         # right child counts
    )


def _split_scan_kernel(
    hist_ref, mask_ref, fbase_ref,
    gr0_ref, f0_ref, thr0_ref, l0_ref, r0_ref,
    gr_ref, f_ref, thr_ref, l_ref, r_ref,
    *, f_blk: int, regression: bool,
):
    """One (tree, feature-block) grid step: score each feature of the
    block and fold its argmax into the running best."""
    fj = pl.program_id(1)

    @pl.when(fj == 0)
    def _seed_from_carry():
        gr_ref[...] = gr0_ref[...]
        f_ref[...] = f0_ref[...]
        thr_ref[...] = thr0_ref[...]
        l_ref[...] = l0_ref[...]
        r_ref[...] = r0_ref[...]

    _, C, B, S = hist_ref.shape
    # tri[b, b'] = [b' <= b]: tri @ h is the prefix sum over bins.
    tri = (
        jax.lax.broadcasted_iota(jnp.int32, (B, B), 1)
        <= jax.lax.broadcasted_iota(jnp.int32, (B, B), 0)
    ).astype(jnp.float32)
    row = jax.lax.broadcasted_iota(jnp.int32, (B - 1, S), 0)
    f_first = fbase_ref[...] + fj * f_blk               # [1, 1] global id
    scorer = variance_gains_from_cumsum if regression else split_gain_ratios_from_cumsum

    def body(f, _):
        h = hist_ref[f]                                 # [C, B, S]
        cum = jnp.stack([
            jnp.dot(tri, h[c], precision=_HIGHEST,
                    preferred_element_type=jnp.float32)
            for c in range(C)
        ])                                              # [C, B, S]
        left, total = cum[:, :B - 1, :], cum[:, B - 1:, :]
        sc = scorer(left, total, axis=0)                # [B-1, S]
        sc = jnp.where(mask_ref[f] > 0, sc, -jnp.inf)

        # Feature argmax with first-occurrence tie-break (== jnp.argmax).
        m = jnp.max(sc, axis=0, keepdims=True)          # [1, S]
        thr = jnp.min(jnp.where(sc == m, row, B - 1), axis=0, keepdims=True)
        # Winner child counts via a one-hot multiply-reduce (TPUs have
        # no fast gather; exact — all other summands are literal zeros).
        one = (row == thr).astype(jnp.float32)
        lcnt = jnp.sum(one[None] * left, axis=1, keepdims=True)   # [C, 1, S]

        # Strictly-greater keeps the earliest (lowest feature-id) winner
        # on ties; `feature < 0` force-accepts the very first feature so
        # the neutral carry never survives.
        better = (m > gr_ref[...]) | (f_ref[...] < 0)   # [1, S]
        gr_ref[...] = jnp.where(better, m, gr_ref[...])
        thr_ref[...] = jnp.where(better, thr, thr_ref[...])
        f_ref[...] = jnp.where(better, f_first + f, f_ref[...])
        l_ref[...] = jnp.where(better, lcnt, l_ref[...])
        r_ref[...] = jnp.where(better, total - lcnt, r_ref[...])
        return 0

    jax.lax.fori_loop(0, f_blk, body, 0)


def split_scan_block(
    hist: jnp.ndarray,           # [tc, S, F, B, C] histogram slab
    mask: jnp.ndarray,           # [tc, F] bool/int feature mask
    carry: tuple | None,         # running best (init_carry or a prior result)
    f_base,                      # global feature id of hist[..., 0, :, :] (traced ok)
    *,
    regression: bool = False,
    f_blk: int | None = None,
    interpret: bool = False,
) -> tuple:
    """Fold one histogram slab into the running-best carry.

    Returns the updated carry ``(gain [tc,S] f32, feature [tc,S] i32,
    threshold [tc,S] i32, left_counts [tc,S,C] f32, right_counts)``.
    ``feature`` ids are global (``f_base`` + position in ``hist``).
    """
    tc, S, F, B, C = hist.shape
    f_blk = choose_score_block(S, B, C, F, f_blk=f_blk)
    Fp = _round_up(F, f_blk)
    if carry is None:
        carry = init_carry(tc, S, C)
    # Kernel layout: [tc, F, C, B, S]; padded features are masked out
    # and can never win (the force-accept lands on feature 0, a real one).
    h = jnp.transpose(hist.astype(jnp.float32), (0, 2, 4, 3, 1))
    h = jnp.pad(h, ((0, 0), (0, Fp - F), (0, 0), (0, 0), (0, 0)))
    mask4 = jnp.pad(mask.astype(jnp.int32), ((0, 0), (0, Fp - F)))[:, :, None, None]
    gr0, f0, thr0, l0, r0 = carry
    row = lambda a: a[:, None, :]                             # [tc, 1, S]
    cls = lambda a: jnp.transpose(a, (0, 2, 1))[:, :, None, :]  # [tc, C, 1, S]

    row_spec = pl.BlockSpec((None, 1, S), lambda t, f: (t, 0, 0))
    cls_spec = pl.BlockSpec((None, C, 1, S), lambda t, f: (t, 0, 0, 0))
    carry_specs = [row_spec, row_spec, row_spec, cls_spec, cls_spec]
    gr, fe, thr, l, r = pl.pallas_call(
        functools.partial(
            _split_scan_kernel, f_blk=f_blk, regression=regression
        ),
        grid=(tc, Fp // f_blk),
        in_specs=[
            pl.BlockSpec((None, f_blk, C, B, S), lambda t, f: (t, f, 0, 0, 0)),
            pl.BlockSpec((None, f_blk, 1, 1), lambda t, f: (t, f, 0, 0)),
            pl.BlockSpec((1, 1), lambda t, f: (0, 0)),
            *carry_specs,
        ],
        out_specs=carry_specs,
        out_shape=[
            jax.ShapeDtypeStruct((tc, 1, S), jnp.float32),
            jax.ShapeDtypeStruct((tc, 1, S), jnp.int32),
            jax.ShapeDtypeStruct((tc, 1, S), jnp.int32),
            jax.ShapeDtypeStruct((tc, C, 1, S), jnp.float32),
            jax.ShapeDtypeStruct((tc, C, 1, S), jnp.float32),
        ],
        interpret=interpret,
    )(
        h, mask4, jnp.full((1, 1), f_base, jnp.int32),
        row(gr0.astype(jnp.float32)), row(f0.astype(jnp.int32)),
        row(thr0.astype(jnp.int32)),
        cls(l0.astype(jnp.float32)), cls(r0.astype(jnp.float32)),
    )
    uncls = lambda a: jnp.transpose(a[:, :, 0, :], (0, 2, 1))  # [tc, S, C]
    return gr[:, 0], fe[:, 0], thr[:, 0], uncls(l), uncls(r)


def split_scan_scores(
    hist: jnp.ndarray,
    mask: jnp.ndarray | None,
    *,
    regression: bool = False,
    f_blk: int | None = None,
    interpret: bool | None = None,
) -> SplitScores:
    """Score a full [tc, S, F, B, C] histogram in one pallas_call.

    This is the ``split_backend="pallas"`` entry point of
    ``core/gain.level_scores`` — used when a combined histogram already
    exists (e.g. post-psum on each shard's feature slice). The
    fully-fused no-HBM-histogram path is ``core/forest.
    fused_level_scores``, which chains ``split_scan_block`` per slab.
    """
    if interpret is None:
        interpret = default_interpret()
    tc, S, F, B, C = hist.shape
    if mask is None:
        mask = jnp.ones((tc, F), jnp.bool_)
    return SplitScores(
        *split_scan_block(
            hist, mask, None, 0,
            regression=regression, f_blk=f_blk, interpret=interpret,
        )
    )
