"""Offline arrow decomposition of a sparse matrix.

Decomposes a square sparse matrix ``A`` (typically a graph adjacency) into
levels ``B_0..B_{K-1}`` with permutations ``sigma_0..sigma_{K-1}`` such
that  ``A = sum_i P_i^T B_i P_i``  where ``P_i`` permutes index ``r`` to
``sigma_i[r]``; equivalently ``B_i = A[sigma_i][:, sigma_i]`` restricted
to level-i edges.  Each ``B_i`` is *arrow-shaped*: nonzeros only in the
first ``width`` rows, the first ``width`` columns, and a band (or the
block diagonal) of width ``width`` around the diagonal.

Host-side algorithm (numpy/scipy), re-designed from the reference's
igraph version (reference arrow/decomposition.py:32-144):
  per level: prune the ``width`` highest-degree vertices to the front,
  linearize the rest by random-spanning-forest DFS, select the edges that
  fit the arrow (vectorized band/block criterion on COO coordinates —
  replacing the reference's per-edge ``es.select`` lambdas, a noted
  hotspot, decomposition.py:84), recurse on the remainder.

The decomposition runs on the host: it is graph preprocessing, not device
code.  The online runtime consumes its output via
``arrow_matrix_tpu.io``.
"""

from __future__ import annotations

import os
import time
from contextlib import contextmanager
from dataclasses import dataclass

import numpy as np
from scipy import sparse

from arrow_matrix_tpu.decomposition.linearize import bfs_order, random_forest_order
from arrow_matrix_tpu.utils.graphs import symmetrize


@contextmanager
def _phase(label: str):
    """Phase timer for the offline pipeline (AMT_DECOMP_PROFILE=1):
    prints per-phase wall seconds to stderr so the scale-ladder rungs
    can attribute decompose time between the native kernels and the
    scipy host work (the optimization targeting data for the
    reference's Julia-layer role)."""
    if not os.environ.get("AMT_DECOMP_PROFILE"):
        yield
        return
    import sys

    t0 = time.perf_counter()
    yield
    print(f"[decomp] {label}: {time.perf_counter() - t0:.2f}s",
          file=sys.stderr, flush=True)


@dataclass
class ArrowLevel:
    """One level of an arrow decomposition.

    matrix:       the permuted, arrow-shaped sparse matrix B_i (CSR).
    permutation:  sigma_i; ``permutation[r]`` is the original index of
                  row r of ``matrix``.
    arrow_width:  the width bound satisfied by ``matrix`` (the last level
                  may exceed the requested width; see
                  ``arrow_decomposition``).
    """

    matrix: sparse.csr_matrix
    permutation: np.ndarray
    arrow_width: int

    @property
    def nonzero_rows(self) -> int:
        """Number of structurally nonzero rows/cols (correct count — the
        reference stores the number of *zero*-degree vertices under this
        name, a known bug; SURVEY.md §7)."""
        sym = self.matrix + self.matrix.T
        return int(np.count_nonzero(np.diff(sym.tocsr().indptr)))

    @property
    def inverse_permutation(self) -> np.ndarray:
        return np.argsort(self.permutation)


def achieved_width(coo_rows: np.ndarray, coo_cols: np.ndarray, width: int) -> int:
    """Smallest band width >= ``width`` covering all edges outside the
    arrow head (rows/cols < width are head edges and always covered)."""
    outside = (coo_rows >= width) & (coo_cols >= width)
    if not np.any(outside):
        return width
    return max(width, int(np.max(np.abs(coo_rows[outside] - coo_cols[outside]))))


def _resolve_backend(backend: str):
    """Pick the linearization implementation.

    ``numpy``: the scipy/csgraph implementation in ``linearize.py``.
    ``native``: the C++ kernels (``native.py``; error if unavailable) —
        the compiled-performance layer, the reference's Julia-module
        role (julia/arrow/*.jl).
    ``auto``: native when it loads, numpy otherwise.
    """
    if backend not in ("auto", "native", "numpy"):
        raise ValueError(f"unknown backend {backend!r}")
    if backend == "numpy":
        return bfs_order, random_forest_order
    from arrow_matrix_tpu.decomposition import native

    if native.available():
        return native.bfs_order, native.random_forest_order
    if backend == "native":
        raise RuntimeError(
            f"backend='native' requested but the native decomposer "
            f"failed to build/load: {native.load_error()}")
    return bfs_order, random_forest_order


def _linear_order(a: sparse.csr_matrix, width: int, deterministic: bool,
                  rng: np.random.Generator,
                  backend: str = "auto") -> np.ndarray:
    """Level ordering: width highest-degree vertices first, then the
    forest-linearized middle, then zero-degree singletons."""
    n = a.shape[0]
    bfs_fn, forest_fn = _resolve_backend(backend)
    from arrow_matrix_tpu.decomposition import native as _native

    # All-native fast path: structure-only symmetrize (the largest
    # single host phase of the v1 profile — scipy's A + A.T carries
    # values the pipeline never reads) feeding the masked forest
    # kernel, no scipy matrix ever built.  Same sorted/deduped
    # structure as symmetrize(), so the resulting decomposition is
    # bit-identical (the v1-vs-v2 parity test pins this).
    native_path = (not deterministic
                   and forest_fn is _native.random_forest_order
                   and n < np.iinfo(np.int32).max)
    if native_path:
        with _phase("symmetrize"):
            sym = _native.symmetrize_structure(a)   # (indptr, indices)
        deg = np.diff(sym[0])
    else:
        with _phase("symmetrize"):
            sym = symmetrize(a)
        deg = np.diff(sym.indptr)

    with _phase("degree-argsort"):
        by_degree = np.argsort(-deg, kind="stable")
    head = by_degree[:width]
    tail = by_degree[width:]
    tail_deg = deg[tail]
    middle = tail[tail_deg > 0]
    singletons = tail[tail_deg == 0]

    if middle.size:
        if native_path:
            # The induced submatrix never materializes — one
            # label-and-filter pass inside the C++ replaces scipy's
            # fancy-indexed sym[middle][:, middle] (saves a full
            # per-level edge copy; PERFORMANCE.md decomposer profile).
            with _phase("forest-native"):
                sub_order = _native.random_forest_order_masked(
                    sym, middle, rng, base_size=min(width - 1, 16))
        else:
            sub = sym[middle][:, middle]
            if deterministic:
                sub_order = bfs_fn(sub)
            else:
                sub_order = forest_fn(sub, rng,
                                      base_size=min(width - 1, 16))
        middle_order = middle[sub_order]
    else:
        middle_order = middle

    order = np.concatenate([head, middle_order, singletons])
    assert order.size == n
    return order.astype(np.int64)


def _single_banded_level(a: sparse.csr_matrix,
                         perm: np.ndarray | None,
                         arrow_width: int) -> ArrowLevel:
    """One-level decomposition of an (optionally reordered) banded
    matrix.  Reports the REQUESTED width — artifacts are saved/loaded
    under the level-0 width, so the tighter achieved bound would break
    the file-naming round-trip — and canonicalizes like every other
    level construction (the tiling builders require it)."""
    if perm is None:
        b = a.copy()
        perm = np.arange(a.shape[0], dtype=np.int64)
    else:
        b = a[perm][:, perm].tocsr()
    b.sum_duplicates()
    b.sort_indices()
    return ArrowLevel(matrix=b, permutation=perm,
                      arrow_width=arrow_width)


def arrow_decomposition(a: sparse.spmatrix,
                        arrow_width: int = 512,
                        max_levels: int = 2,
                        block_diagonal: bool = False,
                        prune: bool = True,
                        seed: int | None = None,
                        backend: str = "numpy",
                        band_detect: bool = True) -> list[ArrowLevel]:
    """Compute an arrow decomposition of a square sparse matrix.

    :param a: square sparse matrix (any scipy format; values preserved).
    :param arrow_width: desired head / band / block width.  The last
        level keeps all remaining edges and may report a larger
        ``arrow_width``.
    :param max_levels: maximum number of levels.
    :param block_diagonal: if True, in-level edges must fall in
        width-by-width blocks on the diagonal (required by the slim
        runtime layout); otherwise a band of width ``arrow_width``.
    :param prune: place the ``arrow_width`` highest-degree vertices first;
        their rows/columns always belong to the level (the arrow head).
    :param seed: RNG seed for the random-spanning-forest linearization.
    :param band_detect: detect banded/bandable inputs (identity or
        reverse-Cuthill-McKee order within ``arrow_width`` of the
        diagonal — the planar/mesh class) and return ONE level with
        zero inter-level routing instead of linearizing.  On by
        default; costs O(nnz) on graphs that fail the gate.
    :param backend: linearization implementation — "numpy" (scipy/
        csgraph; the default), "native" (C++ kernels, the reference's
        Julia-layer role; ~10x faster on large graphs), or "auto"
        (native when available).  The two backends use different RNG
        streams, so for a fixed seed the level structure depends on the
        backend; the default is "numpy" so seeded results never depend
        on toolchain presence — opt into "native"/"auto" for large
        graphs (the reference has the same split between its Python and
        Julia decomposers).
    """
    a = a.tocsr()
    if a.shape[0] != a.shape[1]:
        raise ValueError(f"matrix must be square, got {a.shape}")
    if arrow_width > a.shape[0]:
        raise ValueError(f"arrow_width {arrow_width} exceeds matrix side {a.shape[0]}")

    if backend not in ("auto", "native", "numpy"):
        raise ValueError(f"unknown backend {backend!r}")

    # Already-banded fast path: when every nonzero sits within
    # ``arrow_width`` of the diagonal, the matrix IS a one-level arrow
    # decomposition under the identity permutation (B_0 = A, sigma =
    # id; the runtime tiles a last level banded regardless of the
    # block_diagonal flag).  This is the planar/minor-excluded graph
    # class the reference paper's communication bound targets — e.g. a
    # row-major 2-D grid has bandwidth = side — and the forest
    # linearization would only scramble it into multiple levels with
    # inter-level routing that the natural order never needed.  O(nnz)
    # check; power-law graphs (hub rows reach everywhere) never take
    # it.
    if a.nnz and band_detect:
        coo = a.tocoo()
        # achieved_width at width 0 = the full bandwidth max|r-c| (one
        # band-math implementation for the gate and the per-level
        # accounting).
        bw = achieved_width(coo.row.astype(np.int64),
                            coo.col.astype(np.int64), 0)
        if bw <= arrow_width:
            return [_single_banded_level(a, None, arrow_width)]
        # Bandable under a reordering: reverse Cuthill-McKee (O(nnz),
        # measured 0.9 s at 16.8M nnz) recovers the natural band of a
        # planar/mesh graph in ANY input order.  Necessary-condition
        # pre-gate WITHOUT building A+A^T: deg_sym(i) <= row_deg(i) +
        # col_deg(i), and a band of half-width w holds <= 2w+1 entries
        # per symmetric row, so ub > 2*(2w+1) rejects hub graphs from
        # indptr + one bincount (no matrix construction); graphs that
        # pass pay one symmetrize shared with the RCM call.
        row_deg = np.diff(a.indptr)
        col_deg = np.bincount(coo.col, minlength=a.shape[0])
        ub = int((row_deg + col_deg).max())
        if ub <= 2 * (2 * arrow_width + 1):
            sym = symmetrize(a)
            max_deg = int(np.diff(sym.indptr).max()) if sym.nnz else 0
            if max_deg <= 2 * arrow_width + 1:
                from scipy.sparse import csgraph

                rcm = np.asarray(csgraph.reverse_cuthill_mckee(
                    sym, symmetric_mode=True), dtype=np.int64)
                inv = np.argsort(rcm)
                bw = achieved_width(inv[coo.row], inv[coo.col], 0)
                if bw <= arrow_width:
                    return [_single_banded_level(a, rcm, arrow_width)]

    rng = np.random.default_rng(seed)
    levels: list[ArrowLevel] = []
    _decompose(a, arrow_width, levels, max_levels, block_diagonal, prune, rng,
               backend)
    return levels


def _decompose(a: sparse.csr_matrix, width: int, levels: list[ArrowLevel],
               max_levels: int, block_diagonal: bool, prune: bool,
               rng: np.random.Generator, backend: str = "auto") -> None:
    n = a.shape[0]
    last = len(levels) + 1 >= max_levels

    with _phase("linear-order-total"):
        order = _linear_order(a, width, deterministic=last, rng=rng,
                              backend=backend)
    with _phase("inv-argsort"):
        inv = np.argsort(order)
        if n < np.iinfo(np.int32).max:
            # int32 positions halve the permute/select traffic and save
            # scipy the internal downcast copy its int32-index CSR
            # builders would otherwise make.
            inv = inv.astype(np.int32)

    if not last:
        # Fused native split: one C++ pass replaces the whole
        # tocoo/gather/select/two-CSR-build chain below (~10 s of the
        # 37 s v1 profile at n=2^21).  Bit-identical on duplicate-free
        # inputs (canonical CSR is unique; with duplicate input
        # entries only the f32 summation order can differ, inside the
        # numerics tolerance).  achieved_width is statically `width`
        # here: every non-head in-level edge satisfies |r-c| <= width
        # by the band/block criterion.
        from arrow_matrix_tpu.decomposition import native as _native

        if (backend in ("auto", "native") and _native.available()
                and n < np.iinfo(np.int32).max):
            try:
                with _phase("native-level-split"):
                    b, rest_m = _native.level_split(
                        a, inv, width, block_diagonal, prune)
                levels.append(ArrowLevel(b, order, width))
                if rest_m is not None:
                    _decompose(rest_m, width, levels, max_levels,
                               block_diagonal, prune, rng, backend)
                return
            except _native.LevelSplitUnsupported:
                pass   # numpy path below handles the degenerate cases

    with _phase("coo-permute"):
        coo = a.tocoo()
        r = inv[coo.row]  # positions in the new order
        c = inv[coo.col]

    if not last:
        with _phase("edge-select"):
            if block_diagonal:
                in_level = (r // width) == (c // width)
            else:
                in_level = np.abs(r - c) <= width
            if prune:
                in_level |= (r < width) | (c < width)

            if not np.any(in_level):
                in_level = np.ones(r.size, dtype=bool)

            rest = ~in_level
        with _phase("level-csr-build"):
            b = sparse.csr_matrix(
                (coo.data[in_level], (r[in_level], c[in_level])),
                shape=(n, n))
            b.sum_duplicates()
            b.sort_indices()
        # The all-False fallback above keeps every edge, so the level's
        # width bound is whatever those edges achieve, not the request.
        levels.append(ArrowLevel(b, order,
                                 achieved_width(r[in_level], c[in_level],
                                                width)))

        if np.any(rest):
            # Remainder keeps original indexing; recursion re-linearizes.
            with _phase("rest-csr-build"):
                a_rest = sparse.csr_matrix(
                    (coo.data[rest], (coo.row[rest], coo.col[rest])),
                    shape=(n, n))
            _decompose(a_rest, width, levels, max_levels, block_diagonal,
                       prune, rng, backend)
    else:
        # Last level: keep everything, report the width actually achieved.
        with _phase("level-csr-build"):
            b = sparse.csr_matrix((coo.data, (r, c)), shape=(n, n))
            b.sum_duplicates()
            b.sort_indices()
        levels.append(ArrowLevel(b, order, achieved_width(r, c, width)))


def reconstruct(levels: list[ArrowLevel]) -> sparse.csr_matrix:
    """Un-permute and sum all levels: returns sum_i P_i^T B_i P_i,
    which must equal the decomposed matrix (the core invariant)."""
    n = levels[0].matrix.shape[0]
    total = sparse.csr_matrix((n, n), dtype=levels[0].matrix.dtype)
    for lvl in levels:
        p = lvl.permutation
        coo = lvl.matrix.tocoo()
        total = total + sparse.csr_matrix(
            (coo.data, (p[coo.row], p[coo.col])), shape=(n, n))
    total.sum_duplicates()
    total.sort_indices()
    return total.tocsr()


def decomposition_matrix(levels: list[ArrowLevel]) -> sparse.csr_matrix:
    """The operator a decomposition represents, recomposed in original
    row order: entry (a, b) of level i's matrix lands at
    ``(sigma_i[a], sigma_i[b])``.  ``decomposition_matrix(levels) @ X``
    equals :func:`decomposition_spmm` (up to f32 summation order) at
    one CSR product per call instead of two row gathers per level — the
    validation golden at 2^22 rows."""
    n = levels[0].matrix.shape[0]
    rows, cols, vals = [], [], []
    for lvl in levels:
        coo = sparse.coo_matrix(lvl.matrix)
        perm = np.asarray(lvl.permutation)
        rows.append(perm[coo.row])
        cols.append(perm[coo.col])
        vals.append(coo.data)
    a = sparse.coo_matrix((np.concatenate(vals),
                           (np.concatenate(rows), np.concatenate(cols))),
                          shape=(n, n)).tocsr()
    a.sum_duplicates()
    return a


def decomposition_spmm(levels: list[ArrowLevel], x: np.ndarray) -> np.ndarray:
    """Golden host-side SpMM through the decomposition:
    ``A @ X = sum_i (B_i @ X[sigma_i])[inv sigma_i]``
    (reference tests/test_arrowdecomposition.py:139-156)."""
    out = np.zeros_like(x)
    for lvl in levels:
        partial = lvl.matrix @ x[lvl.permutation]
        out += partial[lvl.inverse_permutation]
    return out
