"""Pure-XLA oracle for the fused split-scan kernel.

Same contract as ``kernel.split_scan_block``: score a histogram slab,
fold the result into a running-best carry with first-occurrence argmax
semantics, report global feature ids via ``f_base``. Numerics come from
the same ``core/gain.py`` ``*_from_cumsum`` scorers the kernel uses, so
the two are bit-identical — the parity bar of
``tests/test_split_backends.py``.
"""
from __future__ import annotations

import jax.numpy as jnp

from ...core.gain import (
    _select_winners, left_total, split_gain_ratios_from_cumsum,
    variance_gains_from_cumsum,
)


def split_scan_ref(
    hist: jnp.ndarray,           # [tc, S, F, B, C]
    mask: jnp.ndarray | None,    # [tc, F] bool
    carry: tuple | None = None,
    f_base: int = 0,
    *,
    regression: bool = False,
) -> tuple:
    """Reference running-best update over one histogram slab.

    Returns ``(gain [tc,S], feature [tc,S] i32 global, threshold,
    left_counts [tc,S,C], right_counts)``.
    """
    cum = jnp.cumsum(hist, axis=-2)
    total = cum[..., -1, :]
    if regression:
        sc = variance_gains_from_cumsum(*left_total(cum))
    else:
        sc = split_gain_ratios_from_cumsum(*left_total(cum))
    if mask is not None:
        sc = jnp.where(mask[:, None, :, None], sc, -jnp.inf)

    w = _select_winners(sc, cum, total)
    f_glob = w.feature + jnp.int32(f_base)
    if carry is None:
        return (w.gain_ratio, f_glob, w.threshold, w.left_counts, w.right_counts)

    gr0, f0, thr0, l0, r0 = carry
    better = (w.gain_ratio > gr0) | (f0 < 0)
    return (
        jnp.where(better, w.gain_ratio, gr0),
        jnp.where(better, f_glob, f0),
        jnp.where(better, w.threshold, thr0),
        jnp.where(better[..., None], w.left_counts, l0),
        jnp.where(better[..., None], w.right_counts, r0),
    )
