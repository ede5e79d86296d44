"""The plain reference and the comparison that decides ``correct``.

The reference multiplies the generated adjacency itself (a float64
scipy CSR with unit values), never the program's decomposition, so it
is independent of the decomposer, the fold and the kernels.  It runs
after the measured window.  ``A^J`` is linear in X, so for weight
vectors ``w`` drawn from the seed ``(A^J X) w == A^J (X w)``: the
reference applies ``A^J`` to the few columns ``X W``, and the program's
whole (n, k) result is compared through ``got @ W``.  Every feature
column enters every comparison, so a wrong, zeroed or misplaced column
shows as well as a wrong row.

X, W and A are non-negative, so every entry of ``A^J X W`` is a sum of
positive terms: the relative gap of each entry is well conditioned, and
the widest one catches a single wrong row.
"""

from __future__ import annotations

import numpy as np
from scipy import sparse

_MASK64 = (1 << 64) - 1
# Rows per block when the (n, k) arrays are reduced on the host.
BLOCK_ROWS = 1 << 20


def rng(seed: int, stream: int) -> np.random.Generator:
    """Generator for one use (``stream``) of the run's ``--seed``; any
    whole number is a valid seed."""
    return np.random.default_rng([seed & _MASK64, stream])


def features(seed: int, n: int, k: int) -> np.ndarray:
    """The input X users send: uniform [0, 1) float32, (n, k)."""
    return rng(seed, 0).random((n, k), dtype=np.float32)


def weights(seed: int, k: int, count: int) -> np.ndarray:
    """(k, count) non-negative float64 weights, drawn from the seed."""
    return rng(seed, 1).random((k, count))


def project(x: np.ndarray, w: np.ndarray) -> np.ndarray:
    """``x @ w`` in float64, a block of rows at a time."""
    out = np.empty((x.shape[0], w.shape[1]), dtype=np.float64)
    for r in range(0, x.shape[0], BLOCK_ROWS):
        out[r:r + BLOCK_ROWS] = np.asarray(x[r:r + BLOCK_ROWS],
                                           dtype=np.float64) @ w
    return out


def reference(indptr: np.ndarray, indices: np.ndarray, z: np.ndarray,
              iterations: int) -> np.ndarray:
    """``A^iterations @ z`` in float64 over the unit adjacency."""
    n = indptr.size - 1
    a = sparse.csr_matrix(
        (np.ones(indices.size, dtype=np.float64), indices, indptr),
        shape=(n, n))
    z = np.asarray(z, dtype=np.float64)
    for _ in range(iterations):
        z = a @ z
    return z


def compare(got: np.ndarray, w: np.ndarray, want: np.ndarray,
            limits: dict) -> dict:
    """Numbers compared, each beside its limit, and whether all hold.

    ``got`` is the program's whole (n, k) result; ``want`` the reference
    for ``got @ w``."""
    n, c = want.shape
    if got.ndim != 2 or got.shape != (n, w.shape[0]):
        return {"ok": False, "numbers": {"shape_mismatch": {
            "value": 1, "limit": 0}}}
    nonfinite = sum(int(np.count_nonzero(~np.isfinite(got[r:r + BLOCK_ROWS])))
                    for r in range(0, n, BLOCK_ROWS))
    with np.errstate(invalid="ignore", over="ignore"):
        gap = (np.abs(project(got, w) - want)
               / np.maximum(np.abs(want), np.finfo(np.float64).tiny))
    # A non-finite gap reads as the largest float, so the result line
    # stays plain JSON.
    gap = float(np.max(np.where(np.isfinite(gap), gap,
                                np.finfo(np.float64).max)))
    numbers = {
        "max_rel_gap": {"value": gap, "limit": limits["max_rel_gap"]},
        "nonfinite": {"value": nonfinite, "limit": 0},
    }
    ok = all(v["value"] <= v["limit"] for v in numbers.values())
    return {"ok": ok, "numbers": numbers}
