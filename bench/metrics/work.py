"""Work the algorithm needs, counted from shapes: operations and HBM bytes.

Each function counts what any implementation of that step must read,
write and compute, not what the program's kernels happen to do (the
T_GR kernel's one-hot contraction, for instance, does far more). A
share of a roofline or of the chip's peak is then

    least time = max(ops / peak ops, bytes / peak bytes)
    share      = least time / measured time

``shapes`` holds N rows, F features, k trees, m features scored per
tree (dimension reduction), B bins, C classes, D depth and the
frontier width. A level ``L`` holds at most ``min(2**L, frontier)``
nodes per tree. Sizes: a bin is 1 byte, a weight, slot, count or node
field 4.
"""
from __future__ import annotations

W = 4  # bytes of a float32 or int32

# Operations to score one candidate split from its prefix sums (C = 2):
# right = total - left (2), two child sizes (2), two entropies of two
# classes (2 x (2 divisions + 2 logs + 2 products + 1 sum)), the weighted
# conditional entropy (3), the gain (1), the split information
# (2 divisions, 2 logs, 2 products, 1 sum) and the ratio (1).
OPS_PER_CANDIDATE = 30


def _nodes(s: dict, level: int) -> int:
    return min(2 ** level, s["frontier"])


def tgr(s: dict) -> tuple[float, float]:
    """T_GR of one job: the dimension-reduction root histogram over all F
    features, then one histogram per level over the m features each tree
    scores. Reads the bins, each tree's weights (and slots, below the
    root), writes the histogram; one add per (tree, row, feature)."""
    N, F, k, m, B, C, D = (s[n] for n in ("N", "F", "k", "m", "B", "C", "D"))
    ops = k * N * F + D * k * N * m
    byts = N * F + k * N * W + k * F * B * C * W
    for L in range(D):
        byts += N * F + 2 * k * N * W + k * _nodes(s, L) * m * B * C * W
    return float(ops), float(byts)


def tns(s: dict) -> tuple[float, float]:
    """T_NS of one job: prefix sums over the bins and the gain ratio of
    every candidate split of every frontier node; reads the histogram,
    writes one split descriptor per node."""
    k, m, B, C, D = (s[n] for n in ("k", "m", "B", "C", "D"))
    ops = byts = 0
    for L in range(D):
        n = k * _nodes(s, L)
        ops += n * m * B * C + OPS_PER_CANDIDATE * n * m * (B - 1)
        byts += n * m * B * C * W + n * (3 + 2 * C) * W
    return float(ops), float(byts)


def route(s: dict) -> tuple[float, float]:
    """Routing rows to child slots, every level: read the split feature's
    bin and the slot, write the new slot; a compare and a select."""
    N, k, D = s["N"], s["k"], s["D"]
    return float(2 * k * N * D), float(D * k * N * (1 + 2 * W))


def oob(s: dict) -> tuple[float, float]:
    """OOB weights (Eq. 8): walk every row down every tree (a bin and a
    node read per level), then read its weight and compare its label."""
    N, k, D = s["N"], s["k"], s["D"]
    return float(k * N * (2 * D + 2)), float(k * N * (D * (1 + W) + 2 * W))


def binning(s: dict) -> tuple[float, float]:
    """Digitizing the raw rows: read N x F floats, write N x F bins, a
    binary search of B edges each."""
    N, F, B = s["N"], s["F"], s["B"]
    return float(N * F * max(B - 1, 1).bit_length()), float(N * F * (W + 1))


def bootstrap(s: dict) -> tuple[float, float]:
    """DSI counts: k x N draws and one count written per (tree, row)."""
    return float(s["k"] * s["N"]), float(s["k"] * s["N"] * W)


def train_job(s: dict) -> tuple[float, float]:
    parts = [f(s) for f in (binning, bootstrap, tgr, tns, route, oob)]
    return sum(p[0] for p in parts), sum(p[1] for p in parts)


def forest_bytes(s: dict) -> int:
    """A node-pool forest: feature, threshold, left child and a C-wide
    vote per pool node (``frontier * D + 2`` nodes per tree)."""
    return s["k"] * (s["frontier"] * s["D"] + 2) * (3 + s["C"]) * W


def traverse(rows: int, s: dict) -> tuple[float, float]:
    """One traversal-and-vote call over ``rows`` binned rows: per (row,
    tree) D compare-and-select steps and C vote adds; reads the bins and
    the forest once, writes C scores per row."""
    k, D, C, F = s["k"], s["D"], s["C"], s["F"]
    return float(rows * k * (2 * D + C)), float(rows * F + forest_bytes(s) + rows * C * W)


def serve(rows: int, passes: int, s: dict) -> tuple[float, float]:
    """Serving ``rows`` in ``passes`` forward passes: binning of the raw
    rows, the traversal and vote, one label written per row."""
    k, D, C, F, B = s["k"], s["D"], s["C"], s["F"], s["B"]
    ops = rows * F * max(B - 1, 1).bit_length() + rows * k * (2 * D + C)
    byts = rows * F * (W + 1) + passes * forest_bytes(s) + rows * W
    return float(ops), float(byts)


def least_seconds(ops: float, byts: float, peaks: dict) -> tuple[float, str]:
    """The roofline's least time and which bound sets it."""
    t_ops = ops / peaks["bf16_flops_per_s"]
    t_bytes = byts / peaks["hbm_bytes_per_s"]
    return (t_ops, "ops") if t_ops >= t_bytes else (t_bytes, "bytes")
