"""The work count and the peaks table."""

import json
import os

import numpy as np
import pytest

from benchmark import dataset, work


def _graph(name):
    with open(os.path.join(dataset.HERE, "configs", name + ".json")) as f:
        return dataset._generate(json.load(f))


@pytest.mark.parametrize("config", ["tiny-ba", "tiny-grid"])
@pytest.mark.parametrize("k,itemsize", [(16, 4), (128, 4), (128, 2)])
def test_work_counts_follow_a(config, k, itemsize):
    a = _graph(config)
    n, nnz = a.shape[0], a.nnz
    assert work.compulsory_bytes(nnz, n, k, itemsize) == (
        4 * nnz + 2 * n * k * itemsize)
    assert work.flops(nnz, k) == 2 * nnz * k
    # The count is A's: one index per stored entry of the generated
    # adjacency, one read of X and one write of Y.
    x_bytes = n * k * itemsize
    assert work.compulsory_bytes(nnz, n, k, itemsize) == (
        a.indices.size * 4 + 2 * x_bytes)


def test_graphs_are_symmetric_unit_adjacencies():
    for name, deg in (("tiny-ba", None), ("tiny-grid", 4)):
        a = _graph(name)
        assert (a != a.T).nnz == 0
        assert np.all(a.data == 1.0)
        assert a.diagonal().sum() == 0
        if deg is not None:
            assert np.diff(a.indptr).max() == deg


def test_grid_scramble_is_a_relabelling():
    a = _graph("tiny-grid")
    side = 64
    # 2 * side * (side - 1) undirected lattice edges, both directions.
    assert a.nnz == 4 * side * (side - 1)
    degs = np.sort(np.diff(a.indptr))
    assert degs[:4].tolist() == [2, 2, 2, 2]


def test_peaks_known_kind():
    p = work.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["bf16_flops_per_s"] == 197e12
    t, bound = work.roofline_seconds(67_108_736, 1 << 22, 128, 4, p)
    assert bound == "bytes"
    assert t == pytest.approx((4 * 67_108_736 + 2 * (1 << 22) * 128 * 4)
                              / 819e9)


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5"])
def test_peaks_unknown_kind_raises(kind):
    with pytest.raises(KeyError, match="no peaks"):
        work.peaks(kind)
