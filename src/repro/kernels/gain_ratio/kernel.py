"""Pallas TPU kernel: fused weighted histograms for T_GR (paper §4.2.1).

This is the production backend of ``core/histograms.level_histograms``
(selected by ``ForestConfig.hist_backend``), not a single-tree demo. A
CPU worker scatters into histogram bins; TPUs have no fast scatter, so
the kernel builds the histograms as **one-hot matmuls on the MXU**, for
a whole *chunk of trees* per ``pallas_call``. The one-hot index factors
into a slot part and a (class, bin) part, so each feature's histogram
is one MXU-shaped contraction over the samples of a block::

    rows[c*B + b, i] = [bin_f(i) = b] * w_t(i) * base(i, c)   [C*B, N_blk]
    slots[s, i]      = [slot_t(i) = s]                          [S,   N_blk]
    hist_f           = rows @ slots^T                           [C*B, S]

Every per-sample operand keeps the samples on the lane axis (bins are
fed transposed, ``[F, N]``), so no block has a unit or a class-sized
lane dimension and the bins row of feature ``f`` is a sublane read. The
slot one-hot depends only on the tree, so it is built once per grid
step and shared by the ``f_blk`` features of the block. The channels
enter the contraction as they are, so classification (one-hot) and
regression (``[1, y, y^2]``) channels run the same kernel.

Grid: ``(tc, F_blocks, N_blocks)`` with the sample axis innermost
(sequential), so each (tree, feature-block) accumulator tile stays
resident in VMEM while sample blocks stream through. The per-tree DSI
weight multiply happens inside the kernel: the ``[tc, N, C]`` weighted
channel tensor is never materialized. The contraction runs at
``Precision.HIGHEST``: the slot one-hot is exact and every product has
one non-zero term, so integer DSI counts accumulate exactly (below
2**24) and histograms are bitwise equal to the ``segment_sum`` backend.

Arbitrary ``N``/``F`` are supported: inputs are padded up to the block
grid with parked samples (``slot = -1`` -> an all-zero one-hot column)
and dummy features (sliced off the output). Block sizes come from
``choose_blocks``; ``choose_blocks(...)[1]`` doubles as the feature-slab
width of the fused T_GR->T_NS loop (``core/histograms.hist_feature_slab``):
slabs that wide see the same ``(n_blk, f_blk)`` grid in the same order,
so per-slab histograms are bit-identical to slices of a one-shot call.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# Per-kernel VMEM working-set budget, shared by the three kernels. The
# TPU v5e compiler caps a kernel's scoped VMEM at 16 MiB unless told
# otherwise ("Scoped allocation ... limit 16.00M"); the block choosers
# keep their estimate of the working set under 3/4 of that. Each
# estimate (``hist_vmem_bytes``, ``split_scan.score_vmem_bytes``,
# ``tree_traverse.traverse_vmem_bytes``) is fitted to the scoped-VMEM
# size the v5e compiler reports for its kernel, and
# tests/test_tpu_compile.py holds it between that size and twice it at
# the main path's widths.
_VMEM_BUDGET = 12 * 2 ** 20

_MXU = 128                      # TPU v5e MXU tile: 128 x 128

_HIGHEST = jax.lax.Precision.HIGHEST


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _tile_words(rows: int, cols: int) -> int:
    """f32 words a [rows, cols] array occupies in VMEM ((8, 128) tiles)."""
    return _round_up(max(rows, 1), 8) * _round_up(max(cols, 1), 128)


def hist_vmem_bytes(n_blk: int, f_blk: int, S: int, B: int, C: int) -> int:
    """Estimated VMEM of one histogram grid step.

    Double-buffered blocks: bins ``[f_blk, n_blk]``, channels
    ``[C, n_blk]``, weights and slots ``[1, n_blk]`` each, and the
    resident ``[f_blk, C*B, S]`` accumulator (unpadded when S = 1).
    Temporaries, as the compiler's reported sizes bear out: the slot
    one-hot ``[S, n_blk]``, plus its HIGHEST operand splits (x2) when
    ``C*B`` spans several MXU tiles and the operand is reused; the
    per-feature rows ``[C*B, n_blk]`` with their splits (x5) only when
    ``S`` spans several MXU tiles (with one tile the compiler streams
    them); and three ``[C*B, S]`` accumulator tiles (old, product, sum).
    """
    CB = C * B
    acc = _round_up(CB, 8) if S == 1 else _tile_words(CB, S)
    blocks = (
        _tile_words(f_blk, n_blk) + _tile_words(C, n_blk)
        + 2 * _tile_words(1, n_blk) + f_blk * acc
    )
    temps = (
        (3 if CB > _MXU else 1) * _tile_words(S, n_blk)
        + (5 * _tile_words(CB, n_blk) if S > _MXU else 0)
        + 3 * _tile_words(CB, S)
    )
    return 4 * (2 * blocks + temps)


def choose_blocks(
    N: int, F: int, S: int, B: int, C: int, *,
    n_blk: int | None = None, f_blk: int | None = None,
    vmem_budget: int = _VMEM_BUDGET,
) -> tuple[int, int]:
    """Pick (n_blk, f_blk) so ``hist_vmem_bytes`` fits the budget.

    ``n_blk`` is a multiple of 128 (the samples are the lane axis of
    every block) and ``f_blk`` a multiple of 8 (the sublane axis of the
    bins block). Both halve from (2048, 32) until the estimate fits,
    features first, and never pad past one block of the real problem.
    """
    n_cap = _round_up(max(N, 1), 128)
    f_cap = _round_up(max(F, 1), 8)
    fixed_f, fixed_n = f_blk is not None, n_blk is not None
    f_blk = min(f_blk if fixed_f else 32, f_cap)
    n_blk = min(_round_up(n_blk, 128) if fixed_n else 2048, n_cap)
    while hist_vmem_bytes(n_blk, f_blk, S, B, C) > vmem_budget:
        if not fixed_f and f_blk > 8:
            f_blk //= 2
        elif not fixed_n and n_blk > 128:
            n_blk = _round_up(n_blk // 2, 128)
        else:
            break
    return n_blk, f_blk


def _hist_kernel(
    bins_ref, base_ref, w_ref, slot_ref, out_ref, *, n_slots, n_bins, f_blk
):
    """One (tree, feature-block, sample-block) grid step."""
    S, B = n_slots, n_bins
    C = base_ref.shape[0]
    n_blk = slot_ref.shape[-1]

    @pl.when(pl.program_id(2) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    # Slot one-hot, shared by every feature of the block. Parked samples
    # (slot -1) and padding get an all-zero column.
    slots = (
        slot_ref[...] == jax.lax.broadcasted_iota(jnp.int32, (S, n_blk), 0)
    ).astype(jnp.float32)                                   # [S, n_blk]
    v = base_ref[...] * w_ref[...]                          # [C, n_blk]
    # Row r of the per-feature operand is (class r // B, bin r % B).
    r = jax.lax.broadcasted_iota(jnp.int32, (C * B, n_blk), 0)
    cls_r = sum((r >= c * B).astype(jnp.int32) for c in range(1, C))
    bin_r = r - cls_r * B
    v_r = jnp.broadcast_to(v[0:1, :], (C * B, n_blk))
    for c in range(1, C):
        v_r = jnp.where(cls_r == c, v[c:c + 1, :], v_r)     # [C*B, n_blk]

    def body(f, _):
        rows = jnp.where(bins_ref[pl.ds(f, 1), :] == bin_r, v_r, 0.0)
        out_ref[f] += jax.lax.dot_general(
            rows, slots,
            dimension_numbers=(((1,), (1,)), ((), ())),     # rows @ slots^T
            precision=_HIGHEST,
            preferred_element_type=jnp.float32,
        )                                                   # [C*B, S]
        return 0

    jax.lax.fori_loop(0, f_blk, body, 0)


def multi_tree_hist_pallas(
    x_bins: jnp.ndarray,    # [N, F] int (any int dtype)
    base: jnp.ndarray,      # [N, C] float32 unweighted channels
    w: jnp.ndarray,         # [tc, N] float32 per-tree DSI weights
    slot: jnp.ndarray,      # [tc, N] int32 frontier slot, -1 = parked
    *,
    n_slots: int,
    n_bins: int,
    n_blk: int | None = None,
    f_blk: int | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Fused multi-tree histograms. Returns [tc, S, F, B, C] float32."""
    N, F = x_bins.shape
    tc = w.shape[0]
    C = base.shape[1]
    S, B = n_slots, n_bins
    n_blk, f_blk = choose_blocks(N, F, S, B, C, n_blk=n_blk, f_blk=f_blk)

    Np, Fp = _round_up(N, n_blk), _round_up(F, f_blk)
    # Samples on the lane axis everywhere; padding samples are parked
    # (zero weight) and padding features are sliced off below.
    bins_t = jnp.pad(x_bins.astype(jnp.int32), ((0, Np - N), (0, Fp - F))).T
    base_t = jnp.pad(base.astype(jnp.float32), ((0, Np - N), (0, 0))).T
    w3 = jnp.pad(w.astype(jnp.float32), ((0, 0), (0, Np - N)))[:, None, :]
    slot3 = jnp.pad(
        slot.astype(jnp.int32), ((0, 0), (0, Np - N)), constant_values=-1
    )[:, None, :]

    row_spec = pl.BlockSpec((None, 1, n_blk), lambda t, f, n: (t, 0, n))
    out = pl.pallas_call(
        functools.partial(_hist_kernel, n_slots=S, n_bins=B, f_blk=f_blk),
        grid=(tc, Fp // f_blk, Np // n_blk),
        in_specs=[
            pl.BlockSpec((f_blk, n_blk), lambda t, f, n: (f, n)),   # bins^T
            pl.BlockSpec((C, n_blk), lambda t, f, n: (0, n)),       # base^T
            row_spec,                                               # w
            row_spec,                                               # slot
        ],
        out_specs=pl.BlockSpec(
            (None, f_blk, C * B, S), lambda t, f, n: (t, f, 0, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((tc, Fp, C * B, S), jnp.float32),
        interpret=interpret,
    )(bins_t, base_t, w3, slot3)

    # [tc, Fp, C*B, S] -> [tc, S, F, B, C], dummy features sliced off.
    hist = jnp.transpose(out.reshape(tc, Fp, C, B, S), (0, 4, 1, 3, 2))
    return hist[:, :, :F]


def hist_pallas_call(
    x_bins: jnp.ndarray,   # [N, F] int (any int dtype)
    wch: jnp.ndarray,      # [N, C] float32 pre-weighted channels
    slot: jnp.ndarray,     # [N] int32
    *,
    n_slots: int,
    n_bins: int,
    n_blk: int | None = None,
    f_blk: int | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Single-tree convenience wrapper. Returns hist [S, F, B, C] float32.

    ``wch`` carries the weights already folded in (the tree weight passed
    to the kernel is 1); the multi-tree entry point is
    ``multi_tree_hist_pallas``.
    """
    N = x_bins.shape[0]
    ones = jnp.ones((1, N), jnp.float32)
    return multi_tree_hist_pallas(
        x_bins, wch, ones, slot[None],
        n_slots=n_slots, n_bins=n_bins, n_blk=n_blk, f_blk=f_blk, interpret=interpret,
    )[0]
