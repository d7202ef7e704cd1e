"""Quickstart: the paper's end-to-end pipeline on synthetic tabular data.

    python examples/quickstart.py [--trees 32] [--depth 7]

Trains PRF (dimension reduction + DSI bootstrap + weighted voting) and
the paper's two comparison baselines, and prints a Fig. 8-style summary.
"""
import argparse
import sys
import time

sys.path.insert(0, "src")

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--trees", type=int, default=32)
    ap.add_argument("--depth", type=int, default=7)
    ap.add_argument("--samples", type=int, default=6000)
    ap.add_argument("--features", type=int, default=400)
    args = ap.parse_args()

    from repro.core import ForestConfig, train_prf
    from repro.core.baselines import train_mlrf_like, train_rf
    from repro.data.tabular import make_classification, train_test_split
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    print(f"dataset: N={args.samples} M={args.features} (high-dim, noisy)")
    x, y = make_classification(
        n_samples=args.samples, n_features=args.features, n_classes=3,
        n_informative=8, n_redundant=4, label_noise=0.1, class_sep=1.2, seed=7,
    )
    xtr, ytr, xte, yte = train_test_split(x, y, 0.25, 0)

    cfg = ForestConfig(
        n_trees=args.trees, max_depth=args.depth, n_bins=32, n_classes=3
    )
    for name, fn in [
        ("PRF  (paper: dimred + weighted vote)", train_prf),
        ("RF   (random subspaces, plain vote)", train_rf),
        ("MLRF (sampled split candidates)",
         lambda a, b, c, seed: train_mlrf_like(a, b, c, seed, sample_budget=300)),
    ]:
        t0 = time.time()
        model = fn(xtr, ytr, cfg, seed=0)
        acc = model.accuracy(xte, yte)
        print(f"{name:42s} acc={acc:.4f}  ({time.time()-t0:.1f}s)")

    model = train_prf(xtr, ytr, cfg, seed=0)
    w = np.asarray(model.forest.tree_weight)
    print(f"\nOOB tree weights (Eq. 8): mean={w.mean():.3f} min={w.min():.3f} "
          f"max={w.max():.3f}")


if __name__ == "__main__":
    main()
