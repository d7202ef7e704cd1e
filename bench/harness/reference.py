"""Plain reference of the Parallel Random Forest, in numpy.

It imports nothing of the program and takes nothing the program made.
It implements the same semantics from the paper and the program's
documented contracts:

* bins: per-feature quantile edges (``np.quantile``, linear), a sample
  lands in bin ``searchsorted(edges, x, side="right")`` with both sides
  in float32;
* DSI: ``counts[t, i]`` = how often row ``i`` is drawn by tree ``t``'s
  bootstrap, the draws being ``jax.random.randint(key, (k, N), 0, N)``
  (the random stream is part of the semantics: the same seed draws the
  same rows);
* dimension reduction (Alg. 3.1): multiway gain ratio of every feature
  at the root, importance ``VI = GR / sum GR``, the top ``k_imp`` kept
  and ``m - k_imp`` drawn by ``jax.random.uniform`` ranks;
* growth: level by level, the split of a node is the first
  ``(feature, threshold)`` (features in ascending id) with the largest
  binary gain ratio over the node's in-bag weighted class histogram;
  a node splits when that gain ratio exceeds ``min_gain`` and it holds
  at least ``min_samples_split`` weighted samples; ``bin > threshold``
  goes right;
* OOB weights (Eq. 8): each tree's accuracy on the rows it never drew,
  0.5 for a tree with none.

Trees are returned in heap order (root 1, children ``2h`` and
``2h + 1``), which is independent of how the program lays out its node
pool. ``precision`` selects the arithmetic: ``"float64"`` for the
reference, ``"bfloat16"`` for the control, which rounds the bin edges,
the binning comparison, the histogram, every step of the gain-ratio
arithmetic and the OOB accuracy to bfloat16.
"""
from __future__ import annotations

import dataclasses

import numpy as np

TINY = 1e-38


def _rounder(precision: str):
    if precision == "float64":
        return lambda a: a
    if precision == "bfloat16":
        import ml_dtypes

        bf16 = ml_dtypes.bfloat16
        return lambda a: np.asarray(a).astype(bf16).astype(np.float64)
    raise ValueError(f"unknown precision {precision!r}")


# ---------------------------------------------------------------------------
# Binning
# ---------------------------------------------------------------------------


def fit_edges(x: np.ndarray, n_bins: int) -> np.ndarray:
    qs = np.linspace(0.0, 1.0, n_bins + 1)[1:-1]
    edges = np.quantile(np.asarray(x), qs, axis=0).T
    return np.maximum.accumulate(edges, axis=1)


def digitize(x: np.ndarray, edges: np.ndarray, precision: str = "float64") -> np.ndarray:
    """uint8 bins; both sides rounded to float32 (bfloat16 for the control)."""
    if precision == "float64":
        xf = np.asarray(x, np.float32)
        ef = np.asarray(edges, np.float32)
    else:
        r = _rounder(precision)
        xf, ef = r(np.asarray(x, np.float32)), r(np.asarray(edges, np.float32))
    out = np.empty(xf.shape, np.uint8)
    for f in range(xf.shape[1]):
        out[:, f] = np.searchsorted(ef[f], xf[:, f], side="right")
    return out


# ---------------------------------------------------------------------------
# DSI bootstrap and dimension reduction (the random streams use jax.random)
# ---------------------------------------------------------------------------


def bootstrap_counts(key, n_trees: int, n_rows: int) -> np.ndarray:
    import jax
    import jax.numpy as jnp

    draws = np.asarray(jax.random.randint(key, (n_trees, n_rows), 0, n_rows, dtype=jnp.int32))
    return np.stack([np.bincount(d, minlength=n_rows) for d in draws]).astype(np.float64)


def _xlogx(p, r):
    safe = np.where(p > 0, p, 1.0)
    return np.where(p > 0, r(p * r(np.log(safe))), 0.0)


def _entropy(counts, axis, r):
    total = r(counts.sum(axis=axis, keepdims=True))
    p = r(counts / np.maximum(total, TINY))
    return r(-r(_xlogx(p, r).sum(axis=axis)))


def multiway_gain_ratio(hist: np.ndarray, r) -> np.ndarray:
    """[..., F, B, C] -> [..., F] (paper Eq. 2-6, one branch per bin)."""
    total = r(hist.sum(axis=-2))
    n = np.maximum(r(total.sum(axis=-1)), TINY)
    h_node = _entropy(total, -1, r)
    p_b = r(r(hist.sum(axis=-1)) / n[..., None])
    h_cond = r(r(p_b * _entropy(hist, -1, r)).sum(axis=-1))
    gain = r(h_node - h_cond)
    split_info = r(-r(_xlogx(p_b, r).sum(axis=-1)))
    return r(gain / np.maximum(split_info, 1e-12))


def _rank_desc(a: np.ndarray) -> np.ndarray:
    """Rank of each entry by descending value, ties by position."""
    order = np.argsort(-a, axis=-1, kind="stable")
    return np.argsort(order, axis=-1, kind="stable")


def select_features(gr: np.ndarray, key, n_selected: int, n_important: int, r) -> np.ndarray:
    import jax

    g = np.maximum(gr, 0.0)
    vi = r(g / np.maximum(r(g.sum(axis=-1, keepdims=True)), TINY))
    top = _rank_desc(vi) < n_important
    u = np.asarray(jax.random.uniform(key, gr.shape), np.float64)
    u = np.where(top, -np.inf, u)
    rest = _rank_desc(u) < (n_selected - n_important)
    return top | rest


def root_gain_ratios(xb: np.ndarray, y: np.ndarray, w: np.ndarray, n_bins: int,
                     n_classes: int, r) -> np.ndarray:
    """[k, F] multiway gain ratio of every feature on every tree's sample."""
    N, F = xb.shape
    B, C = n_bins, n_classes
    base = (np.arange(F, dtype=np.int64)[None, :] * B + xb.astype(np.int64)) * C + y[:, None]
    out = np.empty((w.shape[0], F))
    for t in range(w.shape[0]):
        rows = np.flatnonzero(w[t])
        h = np.bincount(base[rows].ravel(), weights=np.repeat(w[t, rows], F),
                        minlength=F * B * C)
        out[t] = multiway_gain_ratio(r(h.reshape(F, B, C)), r)
    return out


# ---------------------------------------------------------------------------
# Growth
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class HeapForest:
    """Trees in heap order: node ``h`` has children ``2h`` and ``2h + 1``.

    feature  [k, H] int64   split feature, -1 leaf, -2 no such node
    threshold[k, H] int64   left iff bin <= threshold (0 at leaves)
    counts   [k, H, C]      weighted class counts at the node
    weight   [k]            OOB tree weight
    """

    feature: np.ndarray
    threshold: np.ndarray
    counts: np.ndarray
    weight: np.ndarray


def split_scores(hist: np.ndarray, r):
    """Best binary split per node. hist [S, m, B, C] -> (gr, j, thr, left, right)."""
    S, m, B, C = hist.shape
    cum = r(np.cumsum(hist, axis=2))
    left, total = cum[:, :, :-1, :], cum[:, :, -1:, :]
    right = r(total - left)
    n = total.sum(-1)
    n_l, n_r = r(left.sum(-1)), r(right.sum(-1))
    n_tot = np.maximum(r(n), TINY)
    h_node = _entropy(total, -1, r)
    h_cond = r(r(r(n_l / n_tot) * _entropy(left, -1, r)) + r(r(n_r / n_tot) * _entropy(right, -1, r)))
    gain = r(h_node - h_cond)
    p_l, p_r = r(n_l / n_tot), r(n_r / n_tot)
    split_info = r(-r(_xlogx(p_l, r) + _xlogx(p_r, r)))
    gr = r(gain / np.maximum(split_info, 1e-12))
    gr = np.where((n_l > 0) & (n_r > 0), gr, -np.inf)
    flat = gr.reshape(S, m * (B - 1))
    best = np.argmax(flat, axis=1)
    j, thr = best // (B - 1), best % (B - 1)
    s = np.arange(S)
    lc = left[s, j, thr]
    rc = r(total[s, j, 0] - lc)
    return flat[s, best], j, thr, lc, rc, gr, split_info


def grow_tree(xb: np.ndarray, y: np.ndarray, w: np.ndarray, feats: np.ndarray, cfg: dict, r):
    """One tree. feats: ascending ids of the tree's admitted features."""
    B, C, D = cfg["n_bins"], cfg["n_classes"], cfg["max_depth"]
    H = 2 ** (D + 1)
    feature = np.full(H, -2, np.int64)
    threshold = np.zeros(H, np.int64)
    counts = np.zeros((H, C))
    rows = np.flatnonzero(w)
    xt = xb[rows][:, feats].astype(np.int64)           # [n, m]
    yt, wt = y[rows].astype(np.int64), w[rows]
    m = len(feats)
    counts[1] = r(np.bincount(yt, weights=wt, minlength=C))
    feature[1] = -1
    heap = np.array([1])                               # frontier, heap ids
    slot = np.zeros(len(rows), np.int64)               # frontier position, -1 parked
    for _ in range(D):
        S = len(heap)
        live = slot >= 0
        idx = ((slot[live, None] * m + np.arange(m)[None, :]) * B + xt[live]) * C + yt[live, None]
        hist = np.bincount(idx.ravel(), weights=np.repeat(wt[live], m), minlength=S * m * B * C)
        gr, j, thr, lc, rc = split_scores(r(hist.reshape(S, m, B, C)), r)[:5]
        n_node = lc.sum(-1) + rc.sum(-1)
        split = (gr > cfg["min_gain"]) & (n_node >= cfg["min_samples_split"])
        if not split.any():
            break
        sh = heap[split]
        feature[sh] = feats[j[split]]
        threshold[sh] = thr[split]
        feature[2 * sh] = feature[2 * sh + 1] = -1
        counts[2 * sh], counts[2 * sh + 1] = lc[split], rc[split]
        # Route: the k-th split node's children take frontier slots 2k, 2k+1.
        pos = np.full(S, -1, np.int64)
        pos[split] = np.arange(int(split.sum()))
        s_safe = np.where(live, slot, 0)
        go = (xt[np.arange(len(rows)), j[s_safe]] > thr[s_safe]).astype(np.int64)
        p = pos[s_safe]
        slot = np.where(live & (p >= 0), 2 * p + go, -1)
        heap = np.stack([2 * sh, 2 * sh + 1], axis=1).ravel()
    return feature, threshold, counts


def route(feature: np.ndarray, threshold: np.ndarray, xb: np.ndarray, depth: int) -> np.ndarray:
    """Leaf heap id of every row under one heap tree."""
    h = np.ones(xb.shape[0], np.int64)
    rows = np.arange(xb.shape[0])
    for _ in range(depth):
        f = feature[h]
        leaf = f < 0
        go = xb[rows, np.where(leaf, 0, f)] > threshold[h]
        h = np.where(leaf, h, 2 * h + go)
    return h


def oob_weight(feature, threshold, counts, xb, y, w, depth, r=None) -> np.float32:
    """The tree's accuracy on the rows it never drew, rounded by ``r``
    (float32 when None)."""
    oob = np.flatnonzero(w == 0)
    if oob.size == 0:
        return np.float32(0.5)
    leaf = route(feature, threshold, xb[oob], depth)
    label = np.argmax(counts[leaf], axis=-1)
    correct = np.float32(np.sum(label == y[oob]))
    if r is not None:
        return np.float32(r(float(correct) / oob.size))
    return np.float32(correct / np.float32(oob.size))


def train(xb: np.ndarray, y: np.ndarray, w: np.ndarray, masks: np.ndarray, cfg: dict,
          precision: str = "float64") -> HeapForest:
    """Grow every tree and weight it by its OOB accuracy."""
    r = _rounder(precision)
    k = w.shape[0]
    out = [grow_tree(xb, y, w[t], np.flatnonzero(masks[t]), cfg, r) for t in range(k)]
    feature = np.stack([o[0] for o in out])
    threshold = np.stack([o[1] for o in out])
    counts = np.stack([o[2] for o in out])
    weight = np.array([
        oob_weight(feature[t], threshold[t], counts[t], xb, y, w[t], cfg["max_depth"],
                   None if precision == "float64" else r)
        for t in range(k)
    ], np.float32)
    return HeapForest(feature, threshold, counts, weight)


def resolved_sizes(cfg: dict, n_features: int) -> tuple[int, int]:
    """(m, k_imp): ceil(sqrt(M)) features per tree, ceil(sqrt(m)) by importance."""
    import math

    m = min(max(1, math.ceil(math.sqrt(n_features))), n_features)
    return m, min(max(1, math.ceil(math.sqrt(m))), m)


def train_job(x: np.ndarray, y: np.ndarray, cfg: dict, seed: int, *, shards: int = 1,
              precision: str = "float64"):
    """A whole training job from raw rows, as the program's entries define
    the random streams: one bootstrap key for ``shards == 1``
    (``train_prf``), one per data shard of equal rows otherwise (the mesh
    trainer's stratified bootstrap). Returns what the trees are judged
    against: (edges, bins, DSI counts [k, N], feature masks [k, F])."""
    import jax

    B, C, k = cfg["n_bins"], cfg["n_classes"], cfg["n_trees"]
    r = _rounder(precision)
    edges = fit_edges(x, B) if precision == "float64" else r(fit_edges(x, B))
    xb = digitize(x, edges, precision)
    key = jax.random.PRNGKey(seed)
    if shards == 1:
        k_boot, k_dim = jax.random.split(key)
        w = bootstrap_counts(k_boot, k, x.shape[0])
    else:
        n_loc = x.shape[0] // shards
        w = np.concatenate([
            bootstrap_counts(jax.random.split(jax.random.fold_in(key, d))[0], k, n_loc)
            for d in range(shards)
        ], axis=1)
        k_dim = jax.random.fold_in(key, 7)
    m, k_imp = resolved_sizes(cfg, x.shape[1])
    gr = root_gain_ratios(xb, y, w, B, C, r)
    masks = select_features(gr, k_dim, m, k_imp, r)
    return edges, xb, w, masks


# ---------------------------------------------------------------------------
# The program's forest in heap order, and the comparison
# ---------------------------------------------------------------------------


def heap_from_pool(feature, threshold, left_child, class_counts, tree_weight,
                   depth: int) -> HeapForest:
    """Walk a node pool (root at 0, right child = left + 1) into heap order."""
    feature, threshold = np.asarray(feature), np.asarray(threshold)
    left_child, class_counts = np.asarray(left_child), np.asarray(class_counts)
    k, _, C = class_counts.shape
    H = 2 ** (depth + 1)
    hf = np.full((k, H), -2, np.int64)
    ht = np.zeros((k, H), np.int64)
    hc = np.zeros((k, H, C))
    for t in range(k):
        stack = [(0, 1)]
        while stack:
            node, h = stack.pop()
            f = int(feature[t, node])
            hc[t, h] = class_counts[t, node]
            if f < 0 or 2 * h >= H:
                hf[t, h] = -1
                continue
            hf[t, h], ht[t, h] = f, int(threshold[t, node])
            lc = int(left_child[t, node])
            stack += [(lc, 2 * h), (lc + 1, 2 * h + 1)]
    return HeapForest(hf, ht, hc, np.asarray(tree_weight, np.float32))


def _decision_gaps(table, si, can, is_split, f, t, col, min_gain):
    """How far each node's decision lies below the best one there, by the
    reference's gain ratios ``table`` [S, m, B-1] and split infos ``si``.

    A leaf scores ``min_gain``; a split on a feature outside the tree's
    admitted set, on a node too small to split, or at an impossible
    threshold scores -inf. Returns two gaps per node, each clipped to
    [0, 1]: ``rel``, the shortfall as a share of the best, and ``gain``,
    the shortfall times the smaller split info of the two decisions,
    i.e. in units of entropy, where a float32 computation of the gain
    errs by about 1e-7 whatever the node's size."""
    S, B1 = table.shape[0], table.shape[2]
    flat, flat_si = table.reshape(S, -1), si.reshape(S, -1)
    arg = flat.argmax(axis=1)
    top, top_si = flat[np.arange(S), arg], flat_si[np.arange(S), arg]
    best = np.where(can, np.maximum(top, min_gain), min_gain)
    chosen, chosen_si = np.full(S, min_gain), top_si.copy()
    for s_ in np.flatnonzero(is_split):
        j = col.get(int(f[s_]))
        ok = j is not None and can[s_] and 0 <= t[s_] < B1
        chosen[s_] = table[s_, j, t[s_]] if ok else -np.inf
        chosen_si[s_] = si[s_, j, t[s_]] if ok else 1.0
    short = best - chosen
    with np.errstate(invalid="ignore", over="ignore"):
        rel = short / np.maximum(np.abs(best), 1e-12)
        gain = short * np.minimum(top_si, chosen_si)
    clip = lambda a: np.clip(np.nan_to_num(a, nan=1.0, posinf=1.0), 0.0, 1.0)  # noqa: E731
    return clip(rel), clip(gain), best, chosen


def check_tree(xb: np.ndarray, y: np.ndarray, w: np.ndarray, feats: np.ndarray, cfg: dict,
               feature: np.ndarray, threshold: np.ndarray, counts: np.ndarray) -> dict:
    """Walk one grown tree (heap order) with the rows that reach each of
    its nodes under its own splits, and judge every node by the
    reference's rules:

    * ``bad_counts``: nodes whose class counts differ from the weighted
      counts of the in-bag rows that reach them (exact);
    * ``gap``: the widest ``rel`` gap of ``_decision_gaps`` over the
      tree's nodes, and ``gain_gap`` the widest ``gain`` gap; a split
      below the last level reads 1 in both. ``worst`` describes the node
      of the widest ``gain_gap``.
    """
    B, C, D = cfg["n_bins"], cfg["n_classes"], cfg["max_depth"]
    r = _rounder("float64")
    rows = np.flatnonzero(w)
    xr = xb[rows].astype(np.int64)
    xt = xr[:, feats]
    yt, wt = y[rows].astype(np.int64), w[rows]
    col = {int(f): i for i, f in enumerate(feats)}
    m = len(feats)
    node = np.ones(len(rows), np.int64)
    frontier = np.array([1])
    gap, gain_gap, bad, nodes, worst = 0.0, 0.0, 0, 0, None
    for level in range(D + 1):
        S = len(frontier)
        nodes += S
        pos = np.full(2 ** (D + 1), -1, np.int64)
        pos[frontier] = np.arange(S)
        p = pos[node]
        live = p >= 0
        got = np.bincount(p[live] * C + yt[live], weights=wt[live], minlength=S * C).reshape(S, C)
        bad += int(np.sum(np.any(got.astype(np.float32) != counts[frontier].astype(np.float32),
                                 axis=-1)))
        is_split = feature[frontier] >= 0
        if level == D:
            deeper = float(np.any(is_split))
            gap, gain_gap = max(gap, deeper), max(gain_gap, deeper)
            break
        n_node = got.sum(-1)
        idx = ((p[live, None] * m + np.arange(m)[None, :]) * B + xt[live]) * C + yt[live, None]
        hist = np.bincount(idx.ravel(), weights=np.repeat(wt[live], m), minlength=S * m * B * C)
        table, si = split_scores(hist.reshape(S, m, B, C), r)[5:7]
        f, t = feature[frontier], threshold[frontier]
        rel, gg, best, chosen = _decision_gaps(table, si, n_node >= cfg["min_samples_split"],
                                               is_split, f, t, col, cfg["min_gain"])
        gap = max(gap, float(rel.max()))
        i = int(gg.argmax())
        if worst is None or gg[i] > worst["gain_gap"]:
            worst = {"gain_gap": float(gg[i]), "rel_gap": float(rel[i]), "level": level,
                     "rows": float(n_node[i]), "best": float(best[i]),
                     "chosen": float(chosen[i]), "split": bool(is_split[i])}
        gain_gap = max(gain_gap, float(gg[i]))
        # Route the rows by the tree's own splits; rows at leaves stop.
        fn, tn = f[np.maximum(p, 0)], t[np.maximum(p, 0)]
        split_here = live & is_split[np.maximum(p, 0)]
        go = xr[np.arange(len(rows)), np.maximum(fn, 0)] > tn
        node = np.where(split_here, 2 * node + go, 0)     # heap 0: parked
        frontier = np.sort(np.concatenate([2 * frontier[is_split], 2 * frontier[is_split] + 1]))
        if not frontier.size:
            break
    return {"gap": gap, "gain_gap": gain_gap, "bad_counts": bad, "nodes": nodes,
            "worst": worst}


def check_forest(xb, y, w, masks, cfg: dict, got: HeapForest, detail: bool = False) -> dict:
    """``check_tree`` over every tree, plus each tree's OOB weight
    recomputed on its own structure. With ``detail``, also the node of
    the widest gain gap and the rel gap beside it."""
    gap, gain_gap, bad, w_gap, worst = 0.0, 0.0, 0, 0.0, None
    for t in range(w.shape[0]):
        c = check_tree(xb, y, w[t], np.flatnonzero(masks[t]), cfg, got.feature[t],
                       got.threshold[t], got.counts[t])
        gap, bad = max(gap, c["gap"]), bad + c["bad_counts"]
        if c["worst"] is not None and (worst is None or c["gain_gap"] > gain_gap):
            worst = dict(c["worst"], tree=t)
        gain_gap = max(gain_gap, c["gain_gap"])
        ref_w = oob_weight(got.feature[t], got.threshold[t], got.counts[t], xb, y, w[t],
                           cfg["max_depth"])
        w_gap = max(w_gap, abs(float(ref_w) - float(got.weight[t])))
    out = {"split_gap": gain_gap, "count_mismatch_nodes": bad, "weight_gap": w_gap}
    if detail:
        out.update(rel_split_gap=gap, worst_node=worst)
    return out


def first_differences(xb, y, w, masks, cfg: dict, want: HeapForest, got: HeapForest) -> dict:
    """A second witness of what the program's trees differ in, against
    trees the reference grew itself from the same inputs: in each tree,
    the first node (smallest heap id) where the split or leaf differs. Its
    ancestors agree, so the same rows reach it in both trees; both
    decisions are scored there by the reference's float64 gain ratios.
    ``rel`` is the reference's choice minus the program's, as a share of
    the larger: 0 for a mathematical tie."""
    B, C = cfg["n_bins"], cfg["n_classes"]
    r = _rounder("float64")
    diff_nodes, total_nodes, firsts = 0, 0, []
    for t in range(w.shape[0]):
        fa, fb = want.feature[t], got.feature[t]
        ta, tb = want.threshold[t], got.threshold[t]
        differ = (fa != fb) | ((fa >= 0) & (ta != tb))
        total_nodes += int(np.sum(fa != -2))
        diff_nodes += int(np.sum(differ & (fa != -2)))
        if not differ.any():
            continue
        h = int(np.flatnonzero(differ).min())
        feats = np.flatnonzero(masks[t])
        rows = np.flatnonzero(w[t])
        for a in [h >> k for k in range(h.bit_length() - 1, 0, -1)]:
            go = xb[rows, fa[a]] > ta[a]
            rows = rows[go == _goes_right(h, a)]
        xt = xb[rows][:, feats].astype(np.int64)
        yt, wt = y[rows].astype(np.int64), w[t, rows]
        m = len(feats)
        idx = ((np.arange(m)[None, :]) * B + xt) * C + yt[:, None]
        hist = np.bincount(idx.ravel(), weights=np.repeat(wt, m), minlength=m * B * C)
        table = split_scores(r(hist.reshape(1, m, B, C)), r)[5][0]
        col = {int(f): i for i, f in enumerate(feats)}

        def score(f, th):
            if f < 0:
                return cfg["min_gain"]
            return table[col[int(f)], th] if int(f) in col else -np.inf

        a_, b_ = score(fa[h], ta[h]), score(fb[h], tb[h])
        firsts.append({"tree": t, "heap": h, "level": h.bit_length() - 1,
                       "rows": float(wt.sum()), "want": float(a_), "got": float(b_),
                       "rel": float((a_ - b_) / max(abs(a_), abs(b_), 1e-300))})
    return {"nodes_differ_pct": 100.0 * diff_nodes / max(total_nodes, 1),
            "trees_differ": len(firsts),
            "max_rel_at_first_difference": max([abs(f["rel"]) for f in firsts], default=0.0),
            "first_differences": firsts}


def _goes_right(h: int, a: int) -> bool:
    """Whether the path from ancestor ``a`` to heap node ``h`` goes right."""
    child = h >> (h.bit_length() - a.bit_length() - 1)
    return bool(child & 1)


def edge_mismatch(ref_edges: np.ndarray, got_edges: np.ndarray) -> int:
    a, b = np.asarray(ref_edges), np.asarray(got_edges)
    if a.shape != b.shape:
        return int(a.size)
    return int(np.sum(a.astype(np.float32) != b.astype(np.float32)))


# ---------------------------------------------------------------------------
# Prediction (serving)
# ---------------------------------------------------------------------------


def predict_pool(feature, threshold, left_child, class_counts, tree_weight, edges,
                 x: np.ndarray, depth: int, precision: str = "float64",
                 block: int = 65536) -> np.ndarray:
    """Weighted hard vote (Eq. 10) of a node-pool forest over raw rows."""
    feature, threshold = np.asarray(feature), np.asarray(threshold)
    left_child = np.asarray(left_child)
    counts = np.asarray(class_counts, np.float64)
    r = _rounder(precision)
    w = r(np.asarray(tree_weight, np.float64))
    k, _, C = counts.shape
    label = np.argmax(counts, axis=-1)                  # [k, P]
    out = np.empty(x.shape[0], np.int64)
    for s in range(0, x.shape[0], block):
        xb = digitize(x[s:s + block], edges, precision).astype(np.int64)
        n = xb.shape[0]
        rows = np.arange(n)
        score = np.zeros((n, C))
        for t in range(k):
            node = np.zeros(n, np.int64)
            for _ in range(depth):
                f = feature[t, node]
                leaf = f < 0
                go = xb[rows, np.where(leaf, 0, f)] > threshold[t, node]
                node = np.where(leaf, node, left_child[t, node] + go)
            score[rows, label[t, node]] = r(score[rows, label[t, node]] + w[t])
        out[s:s + n] = np.argmax(score, axis=-1)
    return out
