"""Barabasi-Albert graph by the repeated-endpoints pool construction.

A frozen copy of the generator the program ships
(``arrow_matrix_tpu.utils.graphs.barabasi_albert``), kept here so that a
change to the program's generator cannot change the benchmark's data.
For the same ``rows``, ``m`` and seed it draws the same graph.
"""

from __future__ import annotations

import numpy as np
from scipy import sparse


def generate(params: dict, seed: int) -> sparse.csr_matrix:
    """Undirected BA adjacency (both edge directions, unit values) with
    ``params["rows"]`` vertices, each new vertex attaching to
    ``params["m"]`` distinct vertices chosen in proportion to degree."""
    n, m = int(params["rows"]), int(params["m"])
    if n < m + 1:
        raise ValueError(f"need rows > m (got rows={n}, m={m})")
    rng = np.random.default_rng(seed)

    # Every accepted edge adds both endpoints to the pool, so a uniform
    # draw from its filled prefix is a degree-proportional draw.
    pool = np.empty(2 * m * n, dtype=np.int64)
    # Seed star over the first m+1 vertices.
    pool[0:2 * m:2] = np.arange(m)
    pool[1:2 * m:2] = m
    fill = 2 * m

    row = np.empty(m * n, dtype=np.int64)
    col = np.empty(m * n, dtype=np.int64)
    row[:m] = np.arange(m)
    col[:m] = m
    e = m

    for v in range(m + 1, n):
        # Rejection-sample m distinct targets; keep first-seen order.
        picks = pool[rng.integers(0, fill, size=2 * m)]
        while np.unique(picks).size < m:
            picks = np.concatenate(
                [picks, pool[rng.integers(0, fill, size=2 * m)]])
        _, first = np.unique(picks, return_index=True)
        tgt = picks[np.sort(first)][:m]
        row[e:e + m] = v
        col[e:e + m] = tgt
        e += m
        pool[fill:fill + m] = v
        pool[fill + m:fill + 2 * m] = tgt
        fill += 2 * m

    data = np.ones(e, dtype=np.float32)
    a = sparse.csr_matrix((data, (row[:e], col[:e])), shape=(n, n))
    a = (a + a.T).tocsr()
    a.data[:] = 1.0
    a.sum_duplicates()
    a.sort_indices()
    return a
