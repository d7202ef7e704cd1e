"""jit'd public wrappers for the fused tree-traversal kernel."""
from __future__ import annotations

from functools import partial

import jax

from .. import default_interpret
from .kernel import traverse_block
from .ref import traverse_ref

# The production entry points are core/forest.fused_vote_scores (the
# tree-chunked carry loop behind ForestConfig.predict_backend) and the
# serving layer's sharded partial-vote path; both call
# kernel.traverse_block directly and handle backend/interpret
# resolution. This wrapper is the standalone kernel-vs-oracle surface.


@partial(
    jax.jit,
    static_argnames=("depth", "use_pallas", "interpret", "n_blk"),
)
def fused_vote(
    x_binned,
    feature,
    threshold,
    left_child,
    payload,
    carry=None,
    *,
    depth: int,
    use_pallas: bool = True,
    interpret: bool | None = None,
    n_blk: int | None = None,
):
    """Weighted-vote scores [N, C] from a node-pool forest; Pallas or oracle.

    ``interpret=None`` resolves via ``kernels.default_interpret`` (the
    shared rule: emulation off-TPU, compiled on TPU), so backend
    selection cannot diverge across callers — the serving layer's
    sharded path routes through here.
    """
    if not use_pallas:
        return traverse_ref(
            x_binned, feature, threshold, left_child, payload, carry,
            depth=depth,
        )
    if interpret is None:
        interpret = default_interpret()
    return traverse_block(
        x_binned, feature, threshold, left_child, payload, carry,
        depth=depth, n_blk=n_blk, interpret=interpret,
    )
