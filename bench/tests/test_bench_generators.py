"""Every generator is a function of its seed: data rows, job seeds, the
served forest and the request schedule."""
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from harness import drivers  # noqa: E402
from harness.data import make_classification, sub_seed  # noqa: E402

BIG = 2**31 + 987_654_321


def test_rows_deterministic_per_seed():
    a = make_classification(500, 28, 2, seed=sub_seed(BIG, 1))
    b = make_classification(500, 28, 2, seed=sub_seed(BIG, 1))
    c = make_classification(500, 28, 2, seed=sub_seed(BIG + 1, 1))
    assert all(np.array_equal(u, v) for u, v in zip(a, b))
    assert not np.array_equal(a[0], c[0])
    assert a[0].dtype == np.float32 and a[1].dtype == np.int32


def test_sub_seeds_fit_31_bits_and_split_streams():
    seeds = {sub_seed(s, k) for s in (0, 1, BIG, 2**62) for k in (1, 2, 3)}
    assert len(seeds) == 12
    assert all(0 <= s < 2**31 for s in seeds)


CFG = {"n_trees": 3, "max_depth": 3, "n_features": 5, "n_bins": 8, "n_classes": 2}


def test_served_forest_deterministic_and_complete():
    a = [np.asarray(x) for x in drivers.generate_forest(CFG, 11)]
    b = [np.asarray(x) for x in drivers.generate_forest(CFG, 11)]
    c = [np.asarray(x) for x in drivers.generate_forest(CFG, 12)]
    assert all(np.array_equal(u, v) for u, v in zip(a, b))
    assert not np.array_equal(a[0], c[0])
    feature, threshold, left, counts, _, weight = a
    # every tree: root plus 2 + 4 + 8 nodes, leaves at depth 3, weights k/1024
    for t in range(3):
        stack, leaves = [(0, 0)], 0
        while stack:
            node, depth = stack.pop()
            assert counts[t, node].min() >= 1
            if feature[t, node] < 0:
                assert depth == 3
                leaves += 1
                continue
            assert 0 <= feature[t, node] < 5 and 0 <= threshold[t, node] < 7
            stack += [(left[t, node], depth + 1), (left[t, node] + 1, depth + 1)]
        assert leaves == 8
    assert np.all((weight * 1024) % 1 == 0) and np.all((weight >= 0.5) & (weight <= 1.0))


def test_request_schedule_deterministic_per_seed():
    traffic = {"kind": "serve_open", "rate_per_s": 400, "rows_min": 1, "rows_max": 256}

    def schedule(seed):
        d = drivers.load("serve_open")({}, {}, traffic, seed, 2.0, [])
        d.pool = np.zeros((4096, 1), np.float32)
        return [(r.due, r.offset, r.rows) for r in d.schedule()]

    a, b, c = schedule(BIG), schedule(BIG), schedule(BIG + 1)
    assert a == b and a != c
    assert all(0 <= due < 2.0 for due, _, _ in a)
    rows = np.array([rows for _, _, rows in a])
    assert rows.min() >= 1 and rows.max() <= 256 and 1 in rows
    assert len(a) == pytest.approx(800, rel=0.2)
    # log-uniform over 1-256: half of the requests are 16 rows or fewer
    assert np.mean(rows <= 16) == pytest.approx(np.log(17) / np.log(257), abs=0.06)


def test_schedule_needs_a_measured_rate():
    d = drivers.load("serve_open")({}, {}, {"rows_min": 1, "rows_max": 256}, BIG, 2.0, [])
    d.pool = np.zeros((4096, 1), np.float32)
    with pytest.raises(KeyError, match="rate_per_s"):
        d.schedule()
