"""Data-Sampling-Index (DSI) table — the paper's data-multiplexing method (§4.1.2).

The paper's key data-parallel idea: bootstrap sampling never copies data.
A k x N table of sample indexes is broadcast once; every tree's tasks read
the *same* feature subsets through it, so the training-data volume is flat
in the ensemble size k (paper Fig. 14).

On TPU we push the idea one step further: histogram-based training only
needs *how many times* each sample was drawn, so the DSI table collapses
into a ``counts[k, N]`` in-bag weight matrix. The binned dataset is the
single shared copy (N*M bytes); ensemble growth costs k*N extra bytes of
weights — strictly better than the paper's 2*N*M bound (§4.3.2).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .tracing import scope


@partial(jax.jit, static_argnames=("n_trees", "n_samples"))
def make_dsi(key: jax.Array, n_trees: int, n_samples: int) -> jnp.ndarray:
    """Bootstrap index table: [k, N] int32, rows i.i.d. uniform with replacement."""
    return jax.random.randint(key, (n_trees, n_samples), 0, n_samples, dtype=jnp.int32)


@partial(jax.jit, static_argnames=("n_samples",))
def dsi_counts(dsi: jnp.ndarray, n_samples: int) -> jnp.ndarray:
    """Collapse a DSI table into in-bag multiplicity weights.

    Returns counts [k, N] float32; counts[t, i] = #{j : dsi[t, j] == i}.
    """

    def _one(row):
        return jnp.zeros((n_samples,), jnp.float32).at[row].add(1.0)

    return jax.vmap(_one)(dsi)


def oob_mask(counts: jnp.ndarray) -> jnp.ndarray:
    """Out-Of-Bag mask [k, N] bool — samples never drawn by tree t (paper §3.1)."""
    return counts == 0.0


@partial(jax.jit, static_argnames=("n_trees", "n_samples"))
@scope("dsi")
def bootstrap_counts(key: jax.Array, n_trees: int, n_samples: int) -> jnp.ndarray:
    """Fused make_dsi + dsi_counts (never materializes the index table)."""
    dsi = make_dsi(key, n_trees, n_samples)
    return dsi_counts(dsi, n_samples)
