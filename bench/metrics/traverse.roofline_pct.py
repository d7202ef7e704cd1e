"""Traversal kernel (``kernels/tree_traverse``) share of its roofline:
for every kernel call in the trace (``custom-call`` named
``fused_vote_scores``, whose result is ``[C, rows]``), the work of its
rows (``work.traverse``) at the least time, over the calls' device time."""
from metrics import work


def read(rec):
    tr = rec.trace
    if tr is None:
        return None
    calls = [o for o in tr.ops if o.kernel and o.name.startswith("fused_vote_scores")]
    kernel_s = sum(o.dur for o in calls)
    if kernel_s <= 0:
        return None
    least = sum(work.least_seconds(*work.traverse(o.dims()[-1], rec.shapes), rec.peaks)[0]
                for o in calls)
    return 100.0 * least / kernel_s
