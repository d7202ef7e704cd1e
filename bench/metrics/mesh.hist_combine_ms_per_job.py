"""T_GR histogram combine on the mesh (``core/distributed``, the paper's
only large collective) device time per job, mean over the chips: the
all-reduce and reduce-scatter instructions that carry a level histogram.

An instruction carries one when an array of its result holds exactly
``k * S * W * B * C`` elements: every tree's histogram over the level's
``S`` slots (the frontier, or the ``frontier / 2`` rank segments of the
sibling-reuse path) and this chip's ``W`` features (``F / model`` after
a psum, ``F / model / data`` after a reduce-scatter). The compiler may
transpose the array or put it in a tuple with other reductions, so the
count is read and not the dimensions' order."""
import math
import re


def hist_sizes(shapes: dict, mesh: dict) -> set:
    k, F, B, C, S = (shapes[n] for n in ("k", "F", "B", "C", "frontier"))
    local = F // mesh["model"]
    widths = {local, local // mesh["data"]}
    return {k * s * w * B * C for s in (S, max(S // 2, 1)) for w in widths}


def is_hist_combine(op, sizes: set) -> bool:
    if not op.opcode.startswith(("all-reduce", "reduce-scatter")):
        return False
    arrays = re.findall(r"\[([\d,]*)\]", op.shape)
    return any(math.prod(int(d) for d in a.split(",") if d) in sizes for a in arrays)


def read(rec):
    tr, jobs, mesh = rec.trace, len(rec.driver.jobs), rec.traffic.get("mesh")
    if tr is None or not jobs or not mesh:
        return None
    sizes = hist_sizes(rec.shapes, mesh)
    s = tr.op_seconds(lambda o: is_hist_combine(o, sizes))
    return 1e3 * s / tr.n_devices / jobs if s > 0 else None
