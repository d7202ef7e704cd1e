"""Every work-count function against a hand count at a tiny shape:
N=10 rows, F=3 features, k=2 trees, m=2 scored, B=4 bins, C=2 classes,
depth D=2, frontier 4 (level 0 holds 1 node, level 1 holds 2)."""
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from metrics import work  # noqa: E402

S = {"N": 10, "F": 3, "k": 2, "m": 2, "B": 4, "C": 2, "D": 2, "frontier": 4}


def test_tgr():
    # adds: root k*N*F = 60, two levels k*N*m = 40 each
    # bytes: root 30 bins + 80 weights + 192 hist; level 0: 30 + 160 + 2*1*2*4*2*4 = 128;
    # level 1: 30 + 160 + 256
    assert work.tgr(S) == (140.0, 302.0 + 318.0 + 446.0)


def test_tns():
    # level 0: 2 nodes: prefix sums 2*2*4*2 = 32, candidates 2*2*3 = 12 x 30 = 360;
    #          bytes 128 hist + 2 descriptors of 7 words = 56
    # level 1: 4 nodes: 64 + 720; bytes 256 + 112
    assert work.tns(S) == (392.0 + 784.0, 184.0 + 368.0)


def test_route_oob_binning_bootstrap():
    assert work.route(S) == (80.0, 360.0)          # 2 levels x 20 (row, tree) x (1 + 8) bytes
    assert work.oob(S) == (120.0, 360.0)           # 20 x (2 x 2 + 2) ops, 20 x (2 x 5 + 8) bytes
    assert work.binning(S) == (60.0, 150.0)        # 30 cells x 2 compares, x (4 + 1) bytes
    assert work.bootstrap(S) == (20.0, 80.0)


def test_train_job_is_the_sum():
    assert work.train_job(S) == (1596.0, 2568.0)


def test_serving_counts():
    assert work.forest_bytes(S) == 2 * (4 * 2 + 2) * (3 + 2) * 4    # 400
    assert work.traverse(5, S) == (60.0, 15.0 + 400.0 + 40.0)
    assert work.serve(5, 2, S) == (30.0 + 60.0, 75.0 + 800.0 + 20.0)


def test_least_seconds_names_the_bound():
    peaks = {"bf16_flops_per_s": 1e3, "hbm_bytes_per_s": 1e2}
    assert work.least_seconds(90.0, 895.0, peaks) == (pytest.approx(8.95), "bytes")
    assert work.least_seconds(9e4, 895.0, peaks) == (pytest.approx(90.0), "ops")
