"""The mesh trainer against the plain reference on four virtual CPU
devices, on the branch the ``epsilon-train-mesh4`` cell takes on the
chip and the tiny rehearsal does not: the sibling-reuse cache resolves
to off (here by its budget, on the chip by epsilon's 4.2 GB histogram),
and the features each tree scores fall in both ``model`` shards, so the
winner merge and the route psum carry splits from both. Trained through
``fit_bins_sharded``, ``apply_bins`` and ``make_prf_train_fn`` as the
cell does, judged by ``reference.train_job(..., shards=2)`` under the
cell's limits, for both histogram combines."""
import json
import os
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import run  # noqa: E402

CHILD = """
import json, sys
sys.path.insert(0, {bench!r})
import jax, jax.numpy as jnp, numpy as np
from harness import reference as ref
from harness.data import make_classification
from repro.core import ForestConfig
from repro.core.binning import apply_bins
from repro.core.distributed import fit_bins_sharded, make_prf_train_fn
from repro.core.engine import resolve_hist_reuse
from repro.launch.mesh import make_mesh

N, F, K, D, B, SEED = 1024, 256, 4, 3, 64, 2**31 + 15
cfg = ForestConfig(n_trees=K, max_depth=D, n_bins=B, hist_reuse_budget_mb=0,
                   hist_reduce={reduce!r})
x, y = make_classification(N, F, 2, n_informative=12, n_redundant=8, class_sep=1.6,
                           label_noise=0.05, seed=SEED)
mesh = make_mesh((2, 2), ("data", "model"))
edges = fit_bins_sharded(x, B, mesh, sample_block=N // 2, max_size=N // 2)
xb = apply_bins(jnp.asarray(x), jnp.asarray(edges))
train_fn, _ = make_prf_train_fn(cfg, mesh)
forest = train_fn(xb, jnp.asarray(y), jax.random.PRNGKey(SEED % 2**31))
rc = dict(n_bins=B, n_classes=2, max_depth=D, n_trees=K, min_gain=cfg.min_gain,
          min_samples_split=cfg.min_samples_split)
r_edges, r_xb, w, masks = ref.train_job(x, y, rc, SEED % 2**31, shards=2)
pool = [np.asarray(a) for a in (forest.feature, forest.threshold, forest.left_child,
                                 forest.class_counts, forest.tree_weight)]
got = ref.heap_from_pool(*pool, D)
split = got.feature[got.feature >= 0]
print(json.dumps({{
    **ref.check_forest(r_xb, y, w, masks, rc, got),
    "edges_differ": ref.edge_mismatch(r_edges, edges),
    "reuse": bool(resolve_hist_reuse(cfg, F // 2)),
    "scored_per_tree": int(masks.sum(1).max()),
    "scored_in_shard": [int(masks[:, :F // 2].sum()), int(masks[:, F // 2:].sum())],
    "split_in_shard": [int((split < F // 2).sum()), int((split >= F // 2).sum())],
}}))
"""


@pytest.mark.parametrize("reduce", ["psum", "psum_scatter"])
def test_mesh_trainer_matches_reference_without_reuse(reduce):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", CHILD.format(bench=BENCH, reduce=reduce)],
                         env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    r = json.loads(out.stdout.strip().splitlines()[-1])
    limits = run.load_json(BENCH, "configs", "epsilon.json")["limits"]
    assert r["reuse"] is False and r["scored_per_tree"] == 16
    assert min(r["scored_in_shard"]) > 0 and min(r["split_in_shard"]) > 0, r
    assert r["edges_differ"] <= limits["edges_differ"]
    assert r["count_mismatch_nodes"] <= limits["count_mismatch_nodes"]
    assert r["split_gap"] <= limits["split_gap"], r
    assert r["weight_gap"] <= limits["weight_gap"], r
