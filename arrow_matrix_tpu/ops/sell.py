"""SELL (sliced-ELL) — the padding-optimal general SpMM for one chip.

A power-law degree distribution defeats plain ELL (every row pays the
hub degree) and even HYB's two-way split (measured at n=1M BA-8: the
light array pads avg-degree-16 rows to 128 slots and the heavy array
pads 4k rows to the max hub degree — 13x more gathered slots than
nonzeros, and the gather IS the cost on TPU).  SELL-C-sigma, re-derived
for TPU lanes:

  * sigma (row sort by degree) costs nothing at runtime: the framework
    already carries features in an arbitrary permuted order (level-0
    order), so the sort is composed into that permutation once on the
    host and the operator is conjugated into sorted coordinates;
  * the sorted rows are partitioned into *tiers* of whole degree
    values, as many as the geometric ``growth`` rule would make, placed
    to minimise the padded slots (``fold_tiers``): each tier's rows pad
    to its largest degree, and a padded slot costs a gather like a
    real one;
  * each tier is one slot-major (m_t, n_t) ELL computed feature-major
    (ops/ell.py ``ell_spmm_t``: every stored array keeps its large
    dimension minor, and features narrower than the 128-lane tile are
    gathered as lane-packed whole rows), and tier outputs
    **concatenate** — the tiers are contiguous runs of the sorted
    order, so there is no scatter anywhere (TPU scatters serialize;
    concatenation is free).

Binary matrices (graph adjacency) drop the value arrays for per-row
degree masks, halving streamed bytes (``resolve_binary``: one rule
for every packer).

This is the device kernel of the folded single-chip execution
(``MultiLevelArrow(fmt="fold")``), playing the role of the reference's
whole-share cuSPARSE CSRMM (reference arrow/common/sp2cp.py:6-16).
"""

from __future__ import annotations

from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp

import numpy as np
from flax import struct
from scipy import sparse

from arrow_matrix_tpu.io.graphio import CsrLike, num_rows
from arrow_matrix_tpu.obs.metrics import get_registry
from arrow_matrix_tpu.obs.tracer import get_tracer
from arrow_matrix_tpu.ops.ell import (
    ell_spmm_t,
    feature_major_chunk,
    lane_pack,
    slot_runs,
)


@struct.dataclass
class SellMatrix:
    """A matrix in sorted sliced-ELL form, in *sorted* coordinates.

    Row i of this operator is row ``order[i]`` of the source matrix and
    column indices are remapped the same way: callers compose ``order``
    into whatever permutation they already carry (see
    ``sell_from_csr``).  Tier t covers sorted rows
    ``[row_starts[t], row_starts[t+1])`` with ``m_t = cols[t].shape[0]``
    slots.
    """

    cols: Tuple[jax.Array, ...]                    # (m_t, n_t) int32
    data: Optional[Tuple[jax.Array, ...]] = None   # (m_t, n_t), weighted
    deg: Optional[Tuple[jax.Array, ...]] = None    # (n_t,) int32, binary

    n_rows: int = struct.field(pytree_node=False, default=0)
    row_starts: Tuple[int, ...] = struct.field(pytree_node=False,
                                               default=())

    @property
    def binary(self) -> bool:
        return self.data is None

    @property
    def n_slots(self) -> int:
        """Total padded gather slots (the kernel's cost model)."""
        return sum(int(c.shape[0]) * int(c.shape[1]) for c in self.cols)

    def device_nbytes(self) -> int:
        total = 0
        for leaf in jax.tree_util.tree_leaves(self):
            total += leaf.size * leaf.dtype.itemsize
        return total


def tier_boundaries(sorted_aligned_deg: np.ndarray,
                    growth: float = 1.2) -> list[int]:
    """Tier start indices over ascending aligned degrees: a new tier
    starts whenever the degree exceeds ``growth`` times the tier's
    first degree (so within-tier ELL padding is < growth), with the
    zero-degree prefix always its own tier.  The fold uses it only for
    its tier count (:func:`fold_tiers`)."""
    starts = [0]
    n = sorted_aligned_deg.size
    if n == 0:
        return starts
    tier_min = int(sorted_aligned_deg[0])
    # Vectorized walk over the (few) distinct degree values.
    change = np.flatnonzero(np.diff(sorted_aligned_deg)) + 1
    for i in change:
        d = int(sorted_aligned_deg[i])
        if d > growth * tier_min:
            starts.append(int(i))
            tier_min = d
    return starts


def optimal_tier_starts(sorted_deg: np.ndarray, n_tiers: int) -> list[int]:
    """Start indices of at most ``n_tiers`` tiers over ascending degrees
    that minimise the slots ``sum(m_t * n_t)`` (m_t the tier's largest
    degree, n_t its rows), with the zero-degree prefix its own tier.

    A DP over the D distinct nonzero degrees: the fewest slots that
    cover degrees 0..j in g + 1 tiers is the least, over the last
    tier's first degree i, of the best g-tier cover of 0..i-1 plus
    ``deg[j] * rows(i..j)``.  Each of the T steps is a vectorized
    minimum over a (D, D) array, taken in blocks of degrees j of at
    most 2^22 entries: O(T * D^2) time, bounded memory (D = 1,141 at a
    2^22-row BA fold: one block).  Any partition into tiers of
    whole degree values, such as :func:`tier_boundaries`', is a
    candidate, so the result never holds more slots than it at the
    same tier count; with at most ``n_tiers`` distinct degrees every
    degree is a tier of its own and the slots equal the nonzeros.
    Ties go to the earliest start, so the same degrees give the same
    tiers.
    """
    if sorted_deg.size == 0:
        return [0]
    vals, first, counts = np.unique(sorted_deg, return_index=True,
                                    return_counts=True)
    prefix = []
    if vals[0] == 0:                       # the zero-degree tier
        prefix, vals, first, counts = [0], vals[1:], first[1:], counts[1:]
        n_tiers -= 1
    d = vals.size
    if d == 0:
        return prefix
    groups = max(1, min(n_tiers, d))
    if groups == d:
        return prefix + first.tolist()

    vals = vals.astype(np.int64)
    rows = np.concatenate([[0], np.cumsum(counts, dtype=np.int64)])
    block = max(1, (1 << 22) // d)         # degrees j per (j, i) array
    best = vals * rows[1:]                 # one tier over degrees 0..j
    choice = []                            # per step: the last tier's start
    for g in range(1, groups):
        # Starts i >= g: best[i - 1] covers 0..i-1 in g tiers.
        starts_i = np.arange(g, d)
        base = best[None, g - 1:d - 1]
        arg = np.empty(d - g, dtype=np.int64)
        new = np.zeros(d, dtype=np.int64)
        for j0 in range(g, d, block):
            j = np.arange(j0, min(j0 + block, d))
            # best[i - 1] + deg[j] * (rows(0..j) - rows(0..i-1)) over
            # i <= j; deg[j] * rows(0..j) is added after the minimum.
            cost = base - vals[j, None] * rows[None, g:d]
            cost[starts_i[None, :] > j[:, None]] = np.int64(1) << 61
            a = cost.argmin(axis=1)
            arg[j - g] = a
            new[j] = cost[np.arange(j.size), a] + vals[j] * rows[j + 1]
        best = new
        choice.append(arg + g)
    starts, j = [], d - 1
    for g in range(groups - 1, 0, -1):
        j = int(choice[g - 1][j - g])
        starts.append(j)
        j -= 1
    return prefix + first[[0] + starts[::-1]].tolist()


def fold_tiers(sorted_deg: np.ndarray, growth: float = 1.2,
               slot_align: int = 1) -> tuple[np.ndarray, list[int]]:
    """``(aligned, starts)``: the ascending degrees rounded up to
    ``slot_align`` (each tier's slot count is its last row's), and the
    tier starts the fold packs.  ``growth`` fixes the tier count, that
    of :func:`tier_boundaries`; :func:`optimal_tier_starts` places the
    tiers.  The XLA step gathers slot by slot, so alignment above 1
    only adds gathers."""
    aligned = (align_up_vec(sorted_deg, slot_align) if slot_align > 1
               else sorted_deg)
    n_tiers = len(tier_boundaries(aligned, growth))
    return aligned, optimal_tier_starts(aligned, n_tiers)


def sell_from_csr(matrix: CsrLike, pad_rows_to: Optional[int] = None,
                  dtype=np.float32, binary: Union[str, bool] = "auto",
                  growth: float = 1.2, slot_align: int = 1,
                  ) -> tuple[SellMatrix, np.ndarray]:
    """Pack a CSR (or memmapped triplet) into sorted sliced-ELL.

    Returns ``(sell, order)``: ``order[i]`` is the source row stored at
    sorted position i; the operator is fully conjugated (rows AND
    columns) into the sorted coordinates, so a caller carrying features
    ``y[i] = x[order[i]]`` computes ``(A @ x)`` as ``sell @ y`` with no
    runtime permutation at all.

    The host packing is the ``sell.pack`` span of the process tracer
    and records the ``sell.nnz`` / ``sell.slots`` gauges; the device
    copy is ``sell.upload`` (:func:`upload_sell`).
    """
    with get_tracer().span("sell.pack"):
        host, order = _pack(matrix, pad_rows_to, dtype, binary, growth,
                            slot_align)
    return upload_sell(host), order


def upload_sell(sell: SellMatrix) -> SellMatrix:
    """Copy a host-packed SellMatrix to the default device, blocked
    until every tier is resident (the ``sell.upload`` span)."""
    with get_tracer().span("sell.upload"):
        return jax.block_until_ready(jax.tree_util.tree_map(jnp.asarray,
                                                            sell))


def resolve_binary(binary: Union[str, bool], data,
                   nnz: Optional[int] = None,
                   chunk: int = 1 << 24) -> bool:
    """One binary-mode rule: ``data is None`` (memmap implicit ones) is
    always binary; "auto" detects all-ones values; forcing ``True`` on
    non-unit values is an error (the degree mask would silently drop
    them).  ``nnz`` bounds the inspected prefix — value files may carry
    slack beyond ``indptr[-1]`` which must not affect the decision.

    The scan is chunked with early exit so memmapped >RAM value files
    are never materialized at once (the streamed packer's contract,
    ops/arrow_blocks.py ``arrow_blocks_streamed``); weighted data
    usually fails on the first chunk.
    """
    if data is None:
        return True
    if binary is False:
        return False

    def all_ones() -> bool:
        end = len(data) if nnz is None else nnz
        for off in range(0, end, chunk):
            if not np.all(np.asarray(data[off:min(off + chunk, end)])
                          == 1.0):
                return False
        return True

    if binary == "auto":
        return all_ones()
    if not all_ones():
        raise ValueError("binary=True but the matrix has non-unit values")
    return True


def _pack(matrix: CsrLike, pad_rows_to, dtype, binary, growth,
          slot_align) -> tuple[SellMatrix, np.ndarray]:
    """``sell_from_csr``'s host work: ``(sell, order)`` with the tiers
    as numpy arrays."""
    n = num_rows(matrix)
    total = max(pad_rows_to or n, n)
    if isinstance(matrix, sparse.csr_matrix):
        data, indices, indptr = matrix.data, matrix.indices, matrix.indptr
    else:
        data, indices, indptr = matrix
    indptr = np.asarray(indptr, dtype=np.int64)
    degrees = np.zeros(total, dtype=np.int64)
    degrees[:n] = np.diff(indptr)
    is_binary = resolve_binary(binary, data, nnz=int(indptr[-1]))

    order = np.argsort(degrees, kind="stable").astype(np.int64)
    inv_order = np.argsort(order).astype(np.int32)
    aligned, starts = fold_tiers(degrees[order], growth, slot_align)
    starts = starts + [total]

    nnz = int(indptr[-1])
    all_cols = inv_order[np.asarray(indices[:nnz])]
    all_data = (None if is_binary
                else (np.ones(nnz, dtype=dtype) if data is None
                      else np.asarray(data[:nnz]).astype(dtype, copy=False)))

    cols_t, data_t, deg_t = [], [], []
    for lo, hi in zip(starts[:-1], starts[1:]):
        rows = order[lo:hi]                       # source row ids, asc deg
        degs = degrees[rows]
        m_t = int(aligned[hi - 1])                # max aligned deg in tier
        n_t = hi - lo
        cols = np.zeros((m_t, n_t), dtype=np.int32)
        vals = None if is_binary else np.zeros((m_t, n_t), dtype=dtype)
        if m_t and degs.sum():
            # Vectorized fill: flat (slot, tier-local row) coordinates.
            live = degs > 0
            live_rows = rows[live]
            live_degs = degs[live]
            src0 = indptr[live_rows]
            span = np.repeat(src0, live_degs)
            slot = (np.arange(span.size)
                    - np.repeat(np.cumsum(live_degs) - live_degs,
                                live_degs))
            tloc = np.repeat(np.flatnonzero(live), live_degs)
            src = span + slot
            cols[slot, tloc] = all_cols[src]
            if not is_binary:
                vals[slot, tloc] = all_data[src]
        cols_t.append(cols)
        if is_binary:
            deg_t.append(degs.astype(np.int32))
        else:
            data_t.append(vals)

    reg = get_registry()
    reg.gauge("sell.nnz").set(nnz)
    reg.gauge("sell.slots").set(sum(c.size for c in cols_t))
    sell = SellMatrix(
        cols=tuple(cols_t),
        data=None if is_binary else tuple(data_t),
        deg=tuple(deg_t) if is_binary else None,
        n_rows=total,
        row_starts=tuple(int(s) for s in starts[:-1]))
    return sell, order


def align_up_vec(x: np.ndarray, align: int) -> np.ndarray:
    return -(-x // align) * align


def sell_spmm_t(m: SellMatrix, x_t: jax.Array,
                gather_budget: Optional[int] = None,
                chunk: Optional[int] = None) -> jax.Array:
    """``(m @ x_t.T).T`` feature-major: one chunked slot-major ELL per
    tier, outputs concatenated along the (sorted) row axis.

    ``gather_budget`` bounds each tier's gather intermediate
    (k * chunk * n_t elements), the auto-tiling rule shared with the
    other kernels (reference GPU OOM-model tiling,
    spmm_petsc.py:323-395); an explicit ``chunk`` overrides it for
    every tier.

    When k divides the 128-lane tile, the operand is lane-packed once
    here and every tier gathers whole packed rows (``ell_spmm_t``); the
    ``sell.packed_slots`` gauge records the slot-rows that take that
    form (0 when it does not engage), once per trace.  The
    ``sell.gathered_slots`` gauge records the slot-rows the step
    gathers, over each tier's rows: its slots as ``ell_spmm_t`` walks
    them under the tier's chunk (``slot_runs``).
    """
    k = x_t.shape[0]
    packed = lane_pack(x_t)
    reg = get_registry()
    reg.gauge("sell.packed_slots").set(0 if packed is None else m.n_slots)
    outs, gathered = [], 0
    for t, cols in enumerate(m.cols):
        m_t, n_t = cols.shape
        if m_t == 0:
            outs.append(jnp.zeros((k, n_t), dtype=x_t.dtype))
            continue
        c = chunk
        if c is None and gather_budget is not None:
            c = feature_major_chunk(n_t, k, m_t, gather_budget,
                                    jnp.dtype(x_t.dtype).itemsize)
        gathered += n_t * sum(hi - lo for lo, hi, _ in slot_runs(m_t, c))
        outs.append(ell_spmm_t(
            cols, x_t,
            data=None if m.data is None else m.data[t],
            deg=None if m.deg is None else m.deg[t],
            chunk=c, packed=packed))
    reg.gauge("sell.gathered_slots").set(gathered)
    return jnp.concatenate(outs, axis=1)


def sell_stats(m: SellMatrix) -> dict:
    """Per-tier (rows, nnz, slots) of one SellMatrix — the tiers are the
    layout's compute units (each tier is one gather kernel launch), so
    tier skew and padding waste are what obs/imbalance.py summarizes."""
    per_tier = []
    for t, c in enumerate(m.cols):
        m_t, n_t = int(c.shape[0]), int(c.shape[1])
        slots = m_t * n_t
        if m.deg is not None:
            nnz = int(np.asarray(m.deg[t]).sum())
        elif m.data is not None:
            nnz = int(np.count_nonzero(np.asarray(m.data[t])))
        else:
            nnz = slots
        per_tier.append({"rows": n_t, "nnz": nnz, "slots": slots})
    return {
        "n_tiers": len(per_tier),
        "rows": [t["rows"] for t in per_tier],
        "nnz": [t["nnz"] for t in per_tier],
        "slots": [t["slots"] for t in per_tier],
    }
