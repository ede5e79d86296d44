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
  * the sorted rows are partitioned into *tiers* at geometric degree
    boundaries (close a tier when the next aligned degree exceeds
    ``growth`` times the tier's smallest) — padded slots <= growth x
    nonzeros by construction;
  * each tier is one slot-major (m_t, n_t) ELL computed feature-major
    (ops/ell.py ``ell_spmm_t``: every stored array keeps its large
    dimension minor, and features narrower than the 128-lane tile are
    gathered as lane-packed whole rows), and tier outputs
    **concatenate** — the tiers are contiguous runs of the sorted
    order, so there is no scatter anywhere (TPU scatters serialize;
    concatenation is free).

Binary matrices (graph adjacency) drop the value arrays for per-row
degree masks, halving streamed bytes (same rule as ops/hyb.py).

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
    SLOT_ALIGN,
    align_up,
    ell_spmm_t,
    feature_major_chunk,
    lane_pack,
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
    # Default 1.2 measured at n=1M BA-8: 1.25x nnz padded slots over 28
    # tiers, vs 1.61x at growth=1.5 — padded slots ARE the gather cost.
    """Tier start indices over ascending aligned degrees: a new tier
    starts whenever the degree exceeds ``growth`` times the tier's
    first degree (so within-tier ELL padding is < growth), with the
    zero-degree prefix always its own tier."""
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


def sell_from_csr(matrix: CsrLike, pad_rows_to: Optional[int] = None,
                  dtype=np.float32, binary: Union[str, bool] = "auto",
                  growth: float = 1.2, slot_align: int = SLOT_ALIGN,
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


def _pack(matrix: CsrLike, pad_rows_to, dtype, binary, growth,
          slot_align) -> tuple[SellMatrix, np.ndarray]:
    """``sell_from_csr``'s host work: ``(sell, order)`` with the tiers
    as numpy arrays."""
    from arrow_matrix_tpu.ops.hyb import resolve_binary

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
    # slot_align trades physical tile friendliness against LOGICAL
    # slots: tile padding costs no gathers, padded slots do.  Measured
    # at n=2^20 BA-8: align 8 / growth 1.2 -> 21.0M slots (1.25x nnz);
    # align 1 / growth 1.1 -> 17.4M (1.04x) over ~60 tiers — the
    # "fold_tight" bench candidate races the two on chip.
    aligned = (align_up_vec(degrees[order], slot_align)
               if slot_align > 1 else degrees[order])
    starts = tier_boundaries(aligned, growth) + [total]

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
    form (0 when it does not engage), once per trace.
    """
    k = x_t.shape[0]
    packed = lane_pack(x_t)
    get_registry().gauge("sell.packed_slots").set(
        0 if packed is None else m.n_slots)
    outs = []
    for t, cols in enumerate(m.cols):
        m_t, n_t = cols.shape
        if m_t == 0:
            outs.append(jnp.zeros((k, n_t), dtype=x_t.dtype))
            continue
        c = chunk
        if c is None and gather_budget is not None:
            c = feature_major_chunk(n_t, k, m_t, gather_budget,
                                    jnp.dtype(x_t.dtype).itemsize)
        outs.append(ell_spmm_t(
            cols, x_t,
            data=None if m.data is None else m.data[t],
            deg=None if m.deg is None else m.deg[t],
            chunk=c, packed=packed))
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
