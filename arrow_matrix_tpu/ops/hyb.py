"""HYB (split-ELL) whole-level SpMM — the single-chip fast path.

Within one device the arrow block structure buys nothing: the reference
computes a rank's whole share with one general CSRMM (cuSPARSE via
cupy, reference arrow/common/sp2cp.py:6-16); blocking only shapes the
*communication*.  The TPU-native general SpMM is ELL (gathers stream,
the VPU does the masked reduction) — but one power-law hub row would pad
every row's slots to the hub degree.  So split by degree, the classic
HYB layout re-derived for TPU:

  * light rows (degree <= m0): one row-ELL over global columns —
    O(rows x m0) storage, pure chunked gather+reduce;
  * heavy rows (the few hubs): their own compact ELL plus a row-index
    list; results merged by one h-column scatter-add (h ~ hundreds).

m0 is chosen as the smallest aligned slot count that keeps the heavy
list under a row-count cap, so light storage is bounded and the heavy
ELL stays small.

Two TPU-measured layout rules shape the arrays (see ops/ell.py
``ell_spmm_t``): everything is stored slot-major ``(m, rows)`` and
carried feature-major ``(k, N)`` so every stored array keeps its large
dimension minor (a row-major (rows, 8..24) ELL array is physically
padded 5-16x by XLA's (8, 128) tiling — the round-2 compile-OOM at
protocol scale); and binary matrices (graph adjacency —
implicit-ones data, the reference's missing-``_data``-file convention,
graphio.py:298) drop their value arrays entirely in favor of a per-row
degree mask, halving the streamed bytes.

An arrow decomposition's *levels* remain the unit of distribution; HYB
replaces only the per-level device kernel when the level lives on one
chip (``MultiLevelArrow(fmt="hyb")``).  The whole-decomposition folded
operator (``fmt="fold"``) uses the degree-sorted tiered generalization
in ops/sell.py instead, which bounds the ELL padding that HYB's two-way
split still pays on power-law degrees.
"""

from __future__ import annotations

from typing import Optional, Union

import jax
import jax.numpy as jnp

import numpy as np
from flax import struct
from scipy import sparse

from arrow_matrix_tpu.io.graphio import CsrLike, num_rows
from arrow_matrix_tpu.ops.ell import (
    SLOT_ALIGN,
    align_up,
    ell_spmm_t,
    lane_pack,
)


@struct.dataclass
class HybLevel:
    """One matrix in split-ELL form (see module docstring).

    Binary matrices carry ``*_deg`` degree vectors and ``*_data=None``;
    weighted matrices carry ``*_data`` (padding slots zero) and
    ``*_deg=None``.
    """

    light_cols: jax.Array              # (m0, rows) int32, slot-major
    heavy_idx: jax.Array               # (h,) int32 row indices (h may be 0)
    heavy_cols: jax.Array              # (m_h, h) int32, slot-major
    light_data: Optional[jax.Array] = None   # (m0, rows)
    heavy_data: Optional[jax.Array] = None   # (m_h, h)
    light_deg: Optional[jax.Array] = None    # (rows,) int32
    heavy_deg: Optional[jax.Array] = None    # (h,) int32

    n_rows: int = struct.field(pytree_node=False, default=0)

    @property
    def binary(self) -> bool:
        return self.light_data is None

    def device_nbytes(self) -> int:
        total = 0
        for leaf in jax.tree_util.tree_leaves(self):
            total += leaf.size * leaf.dtype.itemsize
        return total


def choose_light_slots(degrees: np.ndarray, heavy_cap: int,
                       align: int = SLOT_ALIGN) -> int:
    """Smallest aligned slot count m0 with at most ``heavy_cap`` rows
    of degree > m0."""
    if degrees.size == 0:
        return 0
    cap = min(max(heavy_cap, 0), degrees.size - 1)
    kth = np.partition(degrees, degrees.size - 1 - cap)[
        degrees.size - 1 - cap]
    return align_up(max(int(kth), 1), align)


def resolve_binary(binary: Union[str, bool], data,
                   nnz: Optional[int] = None,
                   chunk: int = 1 << 24) -> bool:
    """One binary-mode rule: ``data is None`` (memmap implicit ones) is
    always binary; "auto" detects all-ones values; forcing ``True`` on
    non-unit values is an error (the degree mask would silently drop
    them).  ``nnz`` bounds the inspected prefix — value files may carry
    slack beyond ``indptr[-1]`` which must not affect the decision.

    The scan is chunked with early exit so memmapped >RAM value files
    are never materialized at once (the streamed-builder contract,
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


def hyb_from_csr(matrix: CsrLike, pad_rows_to: Optional[int] = None,
                 dtype=np.float32, heavy_cap: Optional[int] = None,
                 binary: Union[str, bool] = "auto") -> HybLevel:
    """Split a CSR (or memmapped triplet) matrix into a HybLevel.

    ``pad_rows_to`` appends empty rows so all levels share one static
    row count; ``heavy_cap`` bounds the heavy list (default: rows/256,
    at least 512); ``binary`` selects the implicit-ones layout
    ("auto" = detect all-ones data).
    """
    n = num_rows(matrix)
    total = max(pad_rows_to or n, n)
    if isinstance(matrix, sparse.csr_matrix):
        data, indices, indptr = matrix.data, matrix.indices, matrix.indptr
    else:
        data, indices, indptr = matrix
    indptr = np.asarray(indptr, dtype=np.int64)
    degrees = np.diff(indptr)
    is_binary = resolve_binary(binary, data, nnz=int(indptr[-1]))
    if heavy_cap is None:
        heavy_cap = max(512, total // 256)
    m0 = choose_light_slots(degrees, heavy_cap)

    heavy_mask = degrees > m0
    heavy_rows = np.flatnonzero(heavy_mask)
    h = heavy_rows.size

    nnz = int(indptr[-1])
    all_cols = np.asarray(indices[:nnz])
    all_data = (None if is_binary
                else (np.ones(nnz, dtype=dtype) if data is None
                      else np.asarray(data[:nnz]).astype(dtype, copy=False)))

    light_cols = np.zeros((m0, total), dtype=np.int32)
    light_data = None if is_binary else np.zeros((m0, total), dtype=dtype)
    light_counts = np.where(heavy_mask, 0, degrees)
    light_deg = light_counts.astype(np.int32) if is_binary else None
    if light_counts.sum():
        starts = np.repeat(indptr[:-1][~heavy_mask],
                           degrees[~heavy_mask])
        slot = (np.arange(starts.size)
                - np.repeat(np.cumsum(degrees[~heavy_mask])
                            - degrees[~heavy_mask],
                            degrees[~heavy_mask]))
        flat = np.repeat(np.arange(n)[~heavy_mask], degrees[~heavy_mask])
        src = starts + slot
        light_cols[slot, flat] = all_cols[src]
        if not is_binary:
            light_data[slot, flat] = all_data[src]

    if h:
        m_h = align_up(int(degrees[heavy_rows].max()), SLOT_ALIGN)
        heavy_cols = np.zeros((m_h, h), dtype=np.int32)
        heavy_data = None if is_binary else np.zeros((m_h, h), dtype=dtype)
        heavy_deg = (degrees[heavy_rows].astype(np.int32) if is_binary
                     else None)
        for out_i, r in enumerate(heavy_rows):
            lo, hi = int(indptr[r]), int(indptr[r + 1])
            heavy_cols[:hi - lo, out_i] = all_cols[lo:hi]
            if not is_binary:
                heavy_data[:hi - lo, out_i] = all_data[lo:hi]
    else:
        heavy_cols = np.zeros((0, 0), dtype=np.int32)
        heavy_data = None if is_binary else np.zeros((0, 0), dtype=dtype)
        heavy_deg = np.zeros((0,), dtype=np.int32) if is_binary else None

    def dev(a):
        return None if a is None else jnp.asarray(a)

    if is_binary:
        light_pad = np.zeros(total - n, dtype=np.int32)
        light_deg = np.concatenate([light_deg, light_pad])

    return HybLevel(
        light_cols=jnp.asarray(light_cols),
        light_data=dev(light_data),
        light_deg=dev(light_deg),
        heavy_idx=jnp.asarray(heavy_rows.astype(np.int32)),
        heavy_cols=jnp.asarray(heavy_cols),
        heavy_data=dev(heavy_data),
        heavy_deg=dev(heavy_deg),
        n_rows=total)


def hyb_spmm_t(level: HybLevel, x_t: jax.Array,
               chunk: Optional[int] = None,
               heavy_chunk: Optional[int] = None) -> jax.Array:
    """``(level @ x_t.T).T`` on feature-major (k, rows) operands — the
    native form: light slot-major ELL gather + compact heavy ELL,
    merged by one h-column scatter-add (heavy rows' light slots are
    empty, so add is exact).  Both gathers share one lane-packed
    operand when k divides the 128-lane tile."""
    packed = lane_pack(x_t)
    out = ell_spmm_t(level.light_cols, x_t, data=level.light_data,
                     deg=level.light_deg, chunk=chunk, packed=packed)
    if level.heavy_idx.shape[0]:
        heavy = ell_spmm_t(level.heavy_cols, x_t, data=level.heavy_data,
                           deg=level.heavy_deg, chunk=heavy_chunk,
                           packed=packed)
        out = out.at[:, level.heavy_idx].add(heavy.astype(out.dtype),
                                             unique_indices=True,
                                             indices_are_sorted=True)
    return out


def hyb_spmm(level: HybLevel, x: jax.Array,
             chunk: Optional[int] = None,
             heavy_chunk: Optional[int] = None) -> jax.Array:
    """Row-major convenience wrapper: ``level @ x`` on (rows, k)
    features.  Pays two transposes around the feature-major kernel —
    fine for tests and the generic multi-level path; hot single-chip
    loops carry features feature-major and call ``hyb_spmm_t`` (or the
    sell kernel) directly."""
    return hyb_spmm_t(level, x.T, chunk=chunk, heavy_chunk=heavy_chunk).T


def hyb_stats(h: HybLevel) -> dict:
    """(rows, nnz, slots) of the light and heavy partitions of one
    HybLevel — the two gather kernels the layout actually launches, and
    the units obs/imbalance.py summarizes for the hyb format."""
    def part(cols, data, deg, rows):
        slots = int(np.asarray(cols.shape).prod())
        if deg is not None:
            nnz = int(np.asarray(deg).sum())
        elif data is not None:
            nnz = int(np.count_nonzero(np.asarray(data)))
        else:
            nnz = slots
        return {"rows": int(rows), "nnz": nnz, "slots": slots}

    light = part(h.light_cols, h.light_data, h.light_deg,
                 h.light_cols.shape[1])
    heavy = part(h.heavy_cols, h.heavy_data, h.heavy_deg,
                 h.heavy_idx.shape[0])
    return {
        "rows": [light["rows"], heavy["rows"]],
        "nnz": [light["nnz"], heavy["nnz"]],
        "slots": [light["slots"], heavy["slots"]],
        "light": light,
        "heavy": heavy,
    }
