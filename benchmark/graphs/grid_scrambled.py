"""2-D 4-neighbour lattice with its vertex labels scrambled by the seed.

The lattice is the adjacency of ``arrow_matrix_tpu.utils.graphs.
grid_graph`` (row-major, side x side).  A seeded permutation relabels
the vertices (rows and columns alike), so the input arrives in no
useful order and the decomposer has to find the band itself.
"""

from __future__ import annotations

import math

import numpy as np
from scipy import sparse


def generate(params: dict, seed: int) -> sparse.csr_matrix:
    """Scrambled ``side x side`` lattice, ``side = sqrt(params["rows"])``."""
    n = int(params["rows"])
    side = math.isqrt(n)
    if side * side != n:
        raise ValueError(f"rows={n} is not a square")
    v = np.arange(n, dtype=np.int64).reshape(side, side)
    # Horizontal and vertical neighbour pairs, both directions.
    src = np.concatenate([v[:, :-1].ravel(), v[:-1, :].ravel()])
    dst = np.concatenate([v[:, 1:].ravel(), v[1:, :].ravel()])
    label = np.random.default_rng(seed).permutation(n)
    rows = label[np.concatenate([src, dst])]
    cols = label[np.concatenate([dst, src])]
    a = sparse.csr_matrix(
        (np.ones(rows.size, dtype=np.float32), (rows, cols)), shape=(n, n))
    a.sum_duplicates()
    a.sort_indices()
    return a
