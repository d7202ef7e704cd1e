"""The control, the reference computed in bfloat16, fails the comparison
that decides ``correct`` at the limits the configurations state, while
the reference read against itself passes. Small sizes on the CPU; the
same control at the cells' own sizes runs on the chip
(``bench/calibrate.py``)."""
import json
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from harness import drivers, reference as ref  # noqa: E402,F401
from harness.data import make_classification  # noqa: E402


def limits(name):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)["limits"]


RC = {"n_bins": 64, "n_classes": 2, "max_depth": 6, "n_trees": 6, "min_gain": 1e-7,
      "min_samples_split": 2}


@pytest.mark.parametrize("shards", [1, 2])
def test_training_control_fails(shards):
    x, y = make_classification(8192, 28, 2, seed=5)
    edges, xb, w, masks = ref.train_job(x, y, RC, 123, shards=shards)
    own = ref.train(xb, y, w, masks, RC)
    same = ref.check_forest(xb, y, w, masks, RC, own)
    assert same == {"split_gap": 0.0, "count_mismatch_nodes": 0, "weight_gap": 0.0}
    _, c_xb, c_w, c_masks = ref.train_job(x, y, RC, 123, shards=shards, precision="bfloat16")
    ctl = ref.check_forest(xb, y, w, masks, RC, ref.train(c_xb, y, c_w, c_masks, RC,
                                                           "bfloat16"))
    lim = limits("higgs")
    assert ctl["count_mismatch_nodes"] > lim["count_mismatch_nodes"]
    assert ctl["split_gap"] > 3 * lim["split_gap"], ctl


def test_serving_control_fails():
    cfg = {"n_trees": 32, "max_depth": 8, "n_features": 28, "n_bins": 64, "n_classes": 2}
    f = [np.asarray(a) for a in drivers.generate_forest(cfg, 3)]
    x, _ = make_classification(20000, 28, 2, seed=9)
    edges = ref.fit_edges(x, 64)
    want = ref.predict_pool(f[0], f[1], f[2], f[3], f[5], edges, x, 8)
    ctl = ref.predict_pool(f[0], f[1], f[2], f[3], f[5], edges, x, 8, precision="bfloat16")
    assert int(np.sum(want != ctl)) > limits("higgs")["label_mismatch_rows"]


def test_witness_scores_the_first_difference():
    """``first_differences`` finds no difference between the reference and
    itself, and at a planted one scores both decisions on the rows that
    reach the node."""
    x, y = make_classification(4096, 28, 2, seed=8)
    _, xb, w, masks = ref.train_job(x, y, RC, 99)
    own = ref.train(xb, y, w, masks, RC)
    same = ref.first_differences(xb, y, w, masks, RC, own, own)
    assert same["trees_differ"] == 0 and same["nodes_differ_pct"] == 0.0
    moved = ref.HeapForest(own.feature.copy(), own.threshold.copy(), own.counts, own.weight)
    split = np.flatnonzero(moved.feature[1] >= 0)
    h = int(split[split > 1].min())                 # a split below the root
    moved.threshold[1, h] += 1
    got = ref.first_differences(xb, y, w, masks, RC, own, moved)
    first = got["first_differences"]
    assert got["trees_differ"] == 1 and first[0]["tree"] == 1 and first[0]["heap"] == h
    assert first[0]["want"] >= first[0]["got"] and first[0]["rows"] > 0
