"""jit'd public wrappers for the fused T_GR histogram kernel."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .. import default_interpret
from .kernel import hist_pallas_call
from .ref import histogram_ref

# The multi-tree production entry point is
# core/histograms.level_histograms(backend="pallas"), which calls
# kernel.multi_tree_hist_pallas directly and handles backend/interpret
# resolution — no second jit wrapper here to keep in lockstep.


@partial(
    jax.jit,
    static_argnames=("n_slots", "n_bins", "use_pallas", "interpret", "n_blk", "f_blk"),
)
def fused_histogram(
    x_bins: jnp.ndarray,
    wch: jnp.ndarray,
    slot: jnp.ndarray,
    *,
    n_slots: int,
    n_bins: int,
    use_pallas: bool = True,
    interpret: bool | None = None,
    n_blk: int | None = None,
    f_blk: int | None = None,
) -> jnp.ndarray:
    """Single-tree hist [S, F, B, C]: the Pallas kernel, or the jnp
    oracle with ``use_pallas=False``. ``interpret=None`` emulates the
    kernel off-TPU and compiles it on TPU."""
    if not use_pallas:
        return histogram_ref(x_bins, wch, slot, n_slots=n_slots, n_bins=n_bins)
    return hist_pallas_call(
        x_bins, wch, slot,
        n_slots=n_slots, n_bins=n_bins, n_blk=n_blk, f_blk=f_blk,
        interpret=default_interpret() if interpret is None else interpret,
    )
