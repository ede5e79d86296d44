"""Numerics policy: accumulation order and the validation tolerance.

One documented policy replacing the ad-hoc 1e-5 / 1e-4 constants that
used to live in bench.py and the CLIs (they now all call
``relative_tolerance``).

Accumulation-order policy
-------------------------
Every SpMM kernel in this framework (`ops/ell.py`, `ops/pallas_blocks.py`)
accumulates in **float32** regardless of storage dtype
(``preferred_element_type=jnp.float32`` on every contraction; the Pallas
kernels carry explicit f32 accumulators), and benchmarks/CLIs pin
``jax_default_matmul_precision="highest"`` so the TPU MXU does not take
its default bfloat16-input passes.  Under that policy the device result
and the host scipy golden (the reference's CPU kernel,
reference arrow/common/sp2cp.py + scipy ``@``) are *exact per addend* and
differ only by the **order** of the additions: XLA is free to reassociate
the slot/block partial sums, scipy accumulates CSR rows sequentially.

Expected error from reassociation alone
---------------------------------------
Summing ``t`` terms in any order gives a relative error bounded by
``(t-1)·eps`` worst-case, and ``O(eps·sqrt(t))`` in the mean for random
signs.  For one SpMM step of ``C = A @ X``, the number of accumulated
terms per output element is the row's nnz; over an iterated run errors
compound at most linearly in the iteration count (each step is applied
to an input already carrying the previous steps' error, and ``A`` is
applied exactly).

``relative_tolerance(row_nnz, iters)`` therefore gates at

    TOL_FACTOR · eps_f32 · sqrt(row_nnz) · iters

with ``TOL_FACTOR = 64`` absorbing the spread between mean and
worst-case orderings plus norm concentration across elements.  Typical
values: row_nnz=16, 1 iter → 3e-5; row_nnz=16, 10 iters → 3e-4 — the
same magnitudes the old hand-picked constants encoded, now derived.

A measured error above the gate means a *wrong kernel*, not unlucky
rounding: reassociation cannot produce errors this large at f32.
"""

from __future__ import annotations

import math

import numpy as np

#: Headroom multiplier over the eps*sqrt(terms) mean-error model.
TOL_FACTOR = 64.0

#: float32 machine epsilon (all kernels accumulate in f32 — see module
#: docstring; storage dtype does not change the accumulator).
EPS_F32 = float(np.finfo(np.float32).eps)


def relative_tolerance(row_nnz: float, iters: int = 1) -> float:
    """Relative-Frobenius-error gate for an iterated SpMM validated
    against the host scipy golden.

    :param row_nnz: accumulation length per output element — use the
        mean nnz per row (``nnz / n``); the sqrt model is a mean-case
        bound and Frobenius norms average over elements.
    :param iters: number of chained SpMM applications between the
        compared states (error compounds at most linearly).
    """
    return TOL_FACTOR * EPS_F32 * math.sqrt(max(float(row_nnz), 1.0)) \
        * max(int(iters), 1)


_SLICE = 1 << 20   # elements per f64 slice in relative_error


def relative_error(got: np.ndarray, want: np.ndarray) -> float:
    """Relative Frobenius error ||got - want|| / ||want|| (the
    reference's validation metric, spmm_15d_main.py:195-197)."""
    # f64 sums over slices: whole-array f64 copies of a 2^22 x 128
    # result would take ~13 GB of host memory.
    got, want = (a.reshape(-1) for a in np.broadcast_arrays(
        np.asarray(got), np.asarray(want)))
    num = den = 0.0
    for s in range(0, got.size, _SLICE):
        g = got[s:s + _SLICE].astype(np.float64)
        w = want[s:s + _SLICE].astype(np.float64)
        g -= w
        num += float(np.dot(g, g))
        den += float(np.dot(w, w))
    return float(np.sqrt(num) / max(np.sqrt(den), 1e-30))
