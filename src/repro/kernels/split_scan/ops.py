"""jit'd public wrappers for the fused split-scan kernel."""
from __future__ import annotations

from functools import partial

import jax

from ...core.gain import SplitScores
from .kernel import split_scan_scores
from .ref import split_scan_ref

# The production entry points are core/gain.level_scores(backend="pallas")
# (full-histogram scoring) and core/forest.fused_level_scores (the
# chained histogram-kernel -> score-kernel path with no HBM histogram);
# both call kernel.split_scan_block / split_scan_scores directly and
# handle backend/interpret resolution.


@partial(
    jax.jit,
    static_argnames=("regression", "use_pallas", "interpret", "f_blk"),
)
def fused_split_scores(
    hist,
    mask=None,
    *,
    regression: bool = False,
    use_pallas: bool = True,
    interpret: bool | None = None,
    f_blk: int | None = None,
) -> SplitScores:
    """SplitScores from a [tc, S, F, B, C] histogram; Pallas or jnp oracle."""
    if not use_pallas:
        return SplitScores(*split_scan_ref(hist, mask, regression=regression))
    return split_scan_scores(
        hist, mask, regression=regression, f_blk=f_blk, interpret=interpret
    )
