"""The roofline count, on an index small enough to work out by hand."""

import numpy as np

import roofline


def test_probes_are_the_nearest_centroids():
    centroids = np.array([[0.0], [1.0], [5.0], [9.0]])
    got = roofline.probes(centroids, np.array([[0.9], [8.0]]), 2)
    assert [sorted(r) for r in got.tolist()] == [[0, 1], [2, 3]]


def test_call_bytes_counts_the_union_once_at_true_sizes():
    sizes = np.array([3, 5, 0, 2])
    probed = np.array([[0, 1], [1, 3]])      # cluster 1 probed twice
    # union {0, 1, 3}: (3 + 5 + 2) rows x 2 code bytes, plus 100 of tables
    assert roofline.call_bytes(sizes, probed, m=2, table_bytes=100) == 120


def test_call_ops():
    sizes = np.array([3, 5, 0, 2])
    probed = np.array([[0, 1], [1, 3]])
    # CL 2*Q*nlist*D = 2*2*4*4; LC 3*Q*P*D*CB = 3*2*2*4*8;
    # DC 2*M*rows = 2*2*(3+5+5+2)
    assert roofline.call_ops(sizes, probed, nlist=4, dim=4, m=2, cb=8) == (
        64 + 384 + 60)


def test_least_seconds_takes_the_larger_bound():
    peak = {"hbm_bytes_per_s": 100.0, "bf16_flops_per_s": 1000.0}
    assert roofline.least_seconds(200, 1000, peak) == 2.0
    assert roofline.least_seconds(10, 5000, peak) == 5.0
