"""Seeded synthetic tabular data, copied from the program's
``repro.data.tabular.make_classification`` so that a later change to the
program cannot move the benchmark's inputs.

Blobs of class-conditional Gaussians on ``n_informative`` features,
``n_redundant`` linear mixes of them, the rest pure noise, then a
``label_noise`` share of labels redrawn. Same seed, same arrays.
"""
from __future__ import annotations

import numpy as np


def make_classification(
    n_samples: int,
    n_features: int,
    n_classes: int,
    n_informative: int = 12,
    n_redundant: int = 8,
    class_sep: float = 1.6,
    label_noise: float = 0.05,
    seed: int = 0,
):
    """Returns (x [N, M] float32, y [N] int32)."""
    rng = np.random.default_rng(seed)
    n_informative = min(n_informative, n_features)
    n_redundant = min(n_redundant, n_features - n_informative)

    centers = rng.normal(0.0, class_sep, (n_classes, n_informative))
    y = rng.integers(0, n_classes, n_samples)
    x_inf = centers[y] + rng.normal(0.0, 1.0, (n_samples, n_informative))

    mix = rng.normal(0.0, 1.0, (n_informative, n_redundant))
    x_red = x_inf @ mix / np.sqrt(n_informative)

    n_noise = n_features - n_informative - n_redundant
    x_noise = rng.normal(0.0, 1.0, (n_samples, n_noise))

    x = np.concatenate([x_inf, x_red, x_noise], axis=1).astype(np.float32)
    perm = rng.permutation(n_features)
    x = x[:, perm]

    flip = rng.random(n_samples) < label_noise
    y = np.where(flip, rng.integers(0, n_classes, n_samples), y)
    return x, y.astype(np.int32)


def sub_seed(seed: int, stream: int) -> int:
    """A 31-bit seed for one stream of a run (data, jobs, requests),
    derived from the run's ``--seed`` of any size."""
    return int(np.random.default_rng([int(seed) % (1 << 63), stream]).integers(0, 2**31 - 1))
