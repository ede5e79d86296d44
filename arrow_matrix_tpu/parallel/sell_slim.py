"""Padding-free distributed layouts: SellSlim and SellMultiLevel.

The stacked-ELL layouts (parallel/arrow_layout.py, multi_level.py)
reproduce the reference's communication structure but store row-major
``(nb, w, m)`` blocks and carry ``(total, k)`` features — layouts the
TPU physically pads 8-16x (PERFORMANCE.md "layout-padding law").  The
classes here are the same distributed algorithms rebuilt on the
padding-free layouts the single-chip fold path proved out:

  * features are carried **feature-major** ``(k, total)``, sharded on
    the row axis (axis 1): the large dimension is minor everywhere;
  * each device's share of a level is **two SELL operators** over its
    local operand — a *body* (its rows >= w: diagonal/banded blocks +
    head-column arm, columns in [shard] ∪ [0, w) ∪ the two w-wide
    shard-edge halos) and a *head* (rows [0, w), columns in its shard)
    whose per-device partials psum into C_0 (reference Reduce,
    arrow_slim_mpi.py:104-119);
  * rows are **tier-grouped by degree per shard** with one shared tier
    shape across devices (shard_map needs one program): tier row
    counts pad to the max over devices, padded rows have degree 0 and
    produce zeros.  The per-shard ordering — zero tier first,
    ascending-degree tiers after, device 0's head rows leading the
    zero tier — is composed into the carried permutation once on the
    host, so it costs nothing at runtime (the fold trick, ops/sell.py).

Communication per level: one masked-psum X_0 broadcast, one psum head
reduction, and two edge ppermutes for the banded halos (reference
nonblocking neighbor exchange, arrow_mpi.py:123-175) — all
orientation-independent.  ``SellMultiLevel`` chains K levels with
composed inter-level reorderings (the reference's Alltoallv feature
movement, arrow_dec_mpi.py:404-550) — by default explicit a2a route
tables (parallel/routing.py; measured lowest comm volume and fastest
wall-clock of every mode), optionally GSPMD-lowered gathers.

Reference counterparts: ``ArrowSlimMPI`` (arrow/arrow_slim_mpi.py) and
``ArrowDecompositionMPI`` (arrow/arrow_dec_mpi.py).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from scipy import sparse

from arrow_matrix_tpu.io.graphio import CsrLike, num_rows
from arrow_matrix_tpu.parallel.mesh import fetch_replicated, put_global
from arrow_matrix_tpu.parallel.multi_level import resolve_feature_dtype
from arrow_matrix_tpu.ops.ell import (
    SLOT_ALIGN,
    align_up,
    block_index_dtype,
    ell_spmm_t,
    feature_major_chunk,
    lane_pack,
)


def degree_ladder(max_deg: int, growth: float = 1.5,
                  align: int = SLOT_ALIGN) -> list[int]:
    """Fixed tier thresholds [0, align, align*g, ...] >= max_deg —
    device-independent, so every shard shares one tier shape."""
    ladder = [0]
    t = align
    while ladder[-1] < max_deg:
        ladder.append(t)
        t = align_up(max(int(t * growth), t + 1), align)
    return ladder


def resolve_ladder(ladder) -> tuple[float, int]:
    """(growth, align) for the shared degree ladder.

    "default" = (1.5, SLOT_ALIGN): few tiers, tile-friendly — but on
    block-diagonal levels whose rows are mostly degree 1-4 the align-8
    floor pads slots 3.45x nnz (measured, n=2^20 BA-8 over 10 levels).
    "tight" = (1.3, 1): ~1.02x nnz LOGICAL slots over ~2x the tiers —
    the gather cost model (gathers iterate logical slots) favors it.
    Honesty note: tiers with m_t < 8 still physically re-pad to the
    8-sublane tile in HBM, so STORAGE bytes shrink less than the slot
    count — the win is compute (gather iterations), not footprint.
    Kept opt-in until a real multi-chip race confirms, mirroring the
    fold_tight candidate.  A (growth, align) tuple sets both
    explicitly.
    """
    if ladder in (None, "default"):
        return (1.5, SLOT_ALIGN)
    if ladder == "tight":
        return (1.3, 1)
    if isinstance(ladder, str) or not hasattr(ladder, "__len__") \
            or len(ladder) != 2:
        raise ValueError(
            f"unknown ladder {ladder!r}: expected 'default', 'tight', "
            f"or a (growth, align) pair")
    growth, align = ladder
    if not float(growth) > 1.0 or int(align) < 1:
        raise ValueError(f"bad ladder {ladder!r}: need growth > 1 "
                         f"and align >= 1")
    return (float(growth), int(align))


@struct.dataclass
class SellShardStack:
    """Per-device-stacked tiered SELL operators (leading device axis).

    ``cols[t]``: (n_dev, m_t, n_t) int32 column indices into the local
    operand; ``deg[t]``: (n_dev, n_t) int32 valid-slot counts (always
    present — they mask tier row padding even in weighted mode);
    ``data[t]``: (n_dev, m_t, n_t) values or None (binary).
    """

    cols: Tuple[jax.Array, ...]
    deg: Tuple[jax.Array, ...]
    data: Optional[Tuple[jax.Array, ...]] = None

    def device_nbytes(self) -> int:
        return sum(leaf.size * leaf.dtype.itemsize
                   for leaf in jax.tree_util.tree_leaves(self))

    @property
    def n_slots(self) -> int:
        """Total padded gather slots across devices and tiers — the
        kernel's cost model (same contract as SellMatrix.n_slots)."""
        return sum(int(np.prod(c.shape)) for c in self.cols)

    def shard_stats(self) -> tuple[np.ndarray, np.ndarray]:
        """Per-device-shard (nnz, slots) summed over tiers, from the
        always-present degree masks — the raw material of the obs
        layer's imbalance report (obs/imbalance.py).  Fetches only the
        small (n_dev, n_t) degree arrays."""
        n_dev = int(self.cols[0].shape[0]) if self.cols else 0
        nnz = np.zeros(n_dev, dtype=np.int64)
        slots = np.zeros(n_dev, dtype=np.int64)
        for t, c in enumerate(self.cols):
            slots += int(np.prod(c.shape[1:], dtype=np.int64))
            nnz += np.asarray(self.deg[t]).sum(axis=1, dtype=np.int64)
        return nnz, slots


def _pack_shard_tiers(shares: list[sparse.csr_matrix], ladder: list[int],
                      binary: bool, dtype,
                      shared_degrees: Optional[np.ndarray] = None
                      ) -> tuple[SellShardStack, np.ndarray, int]:
    """Tier-group each device's share rows by degree under the shared
    ladder; returns (stack, order, rows_out) where ``order[d, i]`` is
    the share row stored at tiered position i of device d (-1 padding)
    and ``rows_out`` = sum of shared tier row counts.

    ``shared_degrees`` keys the buckets and ordering on a
    device-independent degree vector (the head operator: psum'd
    partials need identical row order on every device; local share
    degrees never exceed the global row degree, so the shared tier
    slots always suffice).  It may be a LIST of vectors, one per
    share — the space-shared build flattens (level, device) into one
    share list where each level group shares its own head-degree
    vector but tier shapes must unify across all groups."""
    n_dev = len(shares)
    degs = [np.diff(s.indptr) for s in shares]
    # Stable sort by ladder bucket only: preserves original order
    # within a bucket (device 0's head rows lead the zero tier).
    if shared_degrees is not None:
        per_share = (list(shared_degrees)
                     if isinstance(shared_degrees, (list, tuple))
                     else [shared_degrees] * n_dev)
        bucket = [np.searchsorted(ladder, sd, side="left")
                  for sd in per_share]
        orders = [np.argsort(b, kind="stable") for b in bucket]
    else:
        bucket = [np.searchsorted(ladder, d, side="left") for d in degs]
        orders = [np.argsort(b, kind="stable") for b in bucket]
    # Shared tier row counts = max over devices per bucket.
    n_buckets = len(ladder)
    counts = np.zeros((n_dev, n_buckets), dtype=np.int64)
    for d in range(n_dev):
        np.add.at(counts[d], bucket[d], 1)
    shared = counts.max(axis=0)
    rows_out = int(shared.sum())

    # order[d]: tiered position -> share row (or -1 padding).
    order = np.full((n_dev, rows_out), -1, dtype=np.int64)
    tier_starts = np.concatenate([[0], np.cumsum(shared)])
    for d in range(n_dev):
        sorted_bucket = bucket[d][orders[d]]
        for b in range(n_buckets):
            lo_i = np.searchsorted(sorted_bucket, b, side="left")
            hi_i = np.searchsorted(sorted_bucket, b + 1, side="left")
            rows_b = orders[d][lo_i:hi_i]
            order[d, tier_starts[b]:tier_starts[b] + rows_b.size] = rows_b

    cols_t, deg_t, data_t = [], [], []
    for b in range(n_buckets):
        m_t = ladder[b]
        n_t = int(shared[b])
        lo = int(tier_starts[b])
        cols = np.zeros((n_dev, m_t, n_t), dtype=np.int32)
        deg = np.zeros((n_dev, n_t), dtype=np.int32)
        vals = None if binary else np.zeros((n_dev, m_t, n_t), dtype=dtype)
        for d in range(n_dev):
            # Vectorized tier fill: flat (slot, tier-local row)
            # coordinates, O(tier nnz) numpy work (a per-row Python
            # loop here would dominate protocol-scale builds).
            s = shares[d]
            if getattr(s, "indices", None) is None:
                continue   # _DegreesOnly: a remote shard of the
                # per-host build — its stack slice stays zero pages
                # (never read: put_global materializes only
                # addressable shards)
            rows_b = order[d, lo:lo + n_t]
            live = np.flatnonzero(rows_b >= 0)
            if live.size == 0 or m_t == 0:
                continue
            r_live = rows_b[live]
            degs_live = (s.indptr[r_live + 1] - s.indptr[r_live]).astype(
                np.int64)
            deg[d, live] = degs_live
            nz = degs_live > 0
            if not nz.any():
                continue
            starts_src = s.indptr[r_live[nz]]
            d_nz = degs_live[nz]
            span = np.repeat(starts_src, d_nz)
            slot = (np.arange(span.size)
                    - np.repeat(np.cumsum(d_nz) - d_nz, d_nz))
            tloc = np.repeat(live[nz], d_nz)
            src = span + slot
            cols[d, slot, tloc] = s.indices[src]
            if not binary:
                vals[d, slot, tloc] = s.data[src]
        # Host (numpy) leaves: the callers place the stacks (put_global
        # shards them); a jnp conversion here would upload every
        # remote-shard zero page to the default device first.
        cols_t.append(cols)
        deg_t.append(deg)
        if not binary:
            data_t.append(vals)
    stack = SellShardStack(cols=tuple(cols_t), deg=tuple(deg_t),
                           data=tuple(data_t) if not binary else None)
    return stack, order, rows_out


def mesh_gather_budget(mesh: Mesh) -> int:
    """Per-device bound on one tier's (k, chunk, rows) gather
    intermediate: the fold's rule (``gather_budget_for``) over one
    device's memory budget.  Unbounded, a 2^22-row decomposition at
    k=128 on four v5e chips needs ~19 GB of temporaries per device
    (CPU compile of the a2a step, PR 21) — over the 16 GB HBM."""
    from arrow_matrix_tpu.parallel.multi_level import gather_budget_for
    from arrow_matrix_tpu.utils.platform import device_memory_budget

    return gather_budget_for(device_memory_budget(mesh.devices.flat[0]))


def tier_chunks(stack: SellShardStack, k: int, itemsize: int,
                gather_budget: Optional[int]) -> list:
    """Per tier ``(slots, rows, chunk)`` as :func:`_stack_spmm_t` will
    run it (chunk None = the whole slot axis in one gather)."""
    out = []
    for cols in stack.cols:
        m_t, n_t = int(cols.shape[1]), int(cols.shape[2])
        chunk = (None if gather_budget is None or m_t == 0 else
                 feature_major_chunk(n_t, k, m_t, gather_budget,
                                     itemsize))
        out.append((m_t, n_t, chunk))
    return out


def format_tier_chunks(multi, k: int, itemsize: int) -> str:
    """``--mem_report`` lines for a mesh executor (SellSlim,
    SellMultiLevel, SellSpaceShared — the last holds all levels in one
    stacked body/head): per level, each tier's ``slots x rows:chunk``."""
    budget = multi.gather_budget
    ops = getattr(multi, "ops", multi)
    levels = ops if isinstance(ops, list) else [ops]
    lines = [f"tier gather chunks (k={k}, budget {budget} B/device; "
             f"None = whole slot axis, slots x rows:chunk):"]
    for i, ops in enumerate(levels):
        for part, stack in (("body", ops.body), ("head", ops.head)):
            tiers = tier_chunks(stack, k, itemsize, budget)
            lines.append(f"  level {i} {part}: " + " ".join(
                f"{m}x{n}:{c}" for m, n, c in tiers if m and n))
    return "\n".join(lines)


def _stack_spmm_t(stack: SellShardStack, z_t: jax.Array,
                  gather_budget: Optional[int] = None) -> jax.Array:
    """One device's tiered SpMM: operands carry a leading device axis of
    size 1 inside shard_map.  Returns (k, rows_out).  ``gather_budget``
    bounds each tier's gather intermediate (None: unbounded)."""
    chunks = tier_chunks(stack, z_t.shape[0],
                         jnp.dtype(z_t.dtype).itemsize, gather_budget)
    packed = lane_pack(z_t)
    outs = []
    for t, (cols, (m_t, n_t, chunk)) in enumerate(zip(stack.cols,
                                                       chunks)):
        if m_t == 0:
            outs.append(jnp.zeros((z_t.shape[0], n_t), dtype=z_t.dtype))
            continue
        outs.append(ell_spmm_t(
            cols[0], z_t,
            data=None if stack.data is None else stack.data[t][0],
            deg=stack.deg[t][0], chunk=chunk, packed=packed))
    return jnp.concatenate(outs, axis=1)


@dataclass
class SlimLevelOps:
    """Device-resident operators + host-side maps for one level."""

    body: SellShardStack          # sharded P(axis) on the device axis
    head: SellShardStack
    head_unsort: jax.Array        # (w,) int32, replicated
    orig_pos: jax.Array           # (n_dev, L) int32, sharded: share row
                                  # r -> tiered position (halo sends)
    body_order: np.ndarray        # (n_dev, rows_out) share row / -1
    rows_out: int
    shard_len: int
    n_dev: int
    width: int
    hops: int                     # halo exchange steps (whole shards)
    rem: int                      # rows carried by the farthest hop
    binary: bool

    @property
    def total_out(self) -> int:
        return self.rows_out * self.n_dev

    def device_nbytes(self) -> int:
        return (self.body.device_nbytes() + self.head.device_nbytes()
                + self.orig_pos.size * self.orig_pos.dtype.itemsize)


def as_canonical_csr(matrix: CsrLike) -> sparse.csr_matrix:
    """CSR (or memmapped triplet) -> canonical (duplicate-summed,
    sorted) f32 CSR.  The ONE place the CsrLike forms normalize for
    these layouts — binary-mode detection must run on the canonical
    values (duplicate all-ones entries sum to non-unit weights)."""
    if isinstance(matrix, sparse.csr_matrix):
        a = matrix
    else:
        data, indices, indptr = matrix
        indptr = np.asarray(indptr, dtype=np.int64)
        nnz = int(indptr[-1])
        vals = (np.ones(nnz, dtype=np.float32) if data is None
                else np.asarray(data[:nnz]))
        a = sparse.csr_matrix(
            (vals, np.asarray(indices[:nnz]), indptr),
            shape=(indptr.size - 1, indptr.size - 1))
    a = a.tocsr().astype(np.float32)
    a.sum_duplicates()
    a.sort_indices()
    return a


def as_padded_csr(a: sparse.csr_matrix, total: int) -> sparse.csr_matrix:
    """Canonical CSR padded to (total, total)."""
    if a.shape[0] > total:
        raise ValueError(f"matrix has {a.shape[0]} rows > padded {total}")
    a_pad = a.copy()
    a_pad.resize((total, total))
    return a_pad


class _SliceSource:
    """Canonical row-slice access over an in-memory CSR or a memmapped
    npy triplet, padded to (total, total).

    The sell builders only ever consume row ranges (device shares, the
    head block, the reach scan), so a >RAM memmapped artifact streams
    through at O(slice nnz) host memory — the streaming-loader role of
    the reference (arrow_dec_mpi.py:629-887, graphio.py:449-495) for
    the feature-major layouts.  An in-memory CSR canonicalizes once up
    front; triplets canonicalize per slice (sum_duplicates/sort are
    row-local, so slice-wise == global canonicalization).
    """

    def __init__(self, matrix: CsrLike, n_dev: int, width: int,
                 shard_len: Optional[int] = None):
        if sparse.issparse(matrix):
            a = as_canonical_csr(matrix)
            self.n = a.shape[0]
            self.nnz = int(a.nnz)
            self._trip = None
            self._binary_data = a.data
        else:
            data, indices, indptr = matrix
            self.n = len(indptr) - 1
            self.nnz = int(np.asarray(indptr[-1]))
            self._trip = (data, indices, indptr)
            # Raw values: decomposition artifacts are written canonical
            # (no duplicates), and rows() rejects duplicate slices
            # loudly, so raw == canonical here (same contract as the
            # stacked streamed builder, ops/arrow_blocks.py
            # resolve_blocks_binary).
            self._binary_data = data
        self.n_dev = n_dev
        if shard_len is None:
            shard_len = max(align_up(-(-self.n // n_dev), width), width)
        self.shard_len = shard_len
        self.total = shard_len * n_dev
        if self.n > self.total:
            raise ValueError(
                f"matrix has {self.n} rows > padded {self.total}")
        if sparse.issparse(matrix):
            self._csr = as_padded_csr(a, self.total)
        else:
            self._csr = None

    def resolve_binary(self, binary) -> bool:
        from arrow_matrix_tpu.ops.sell import resolve_binary

        return resolve_binary(binary, self._binary_data, nnz=self.nnz)

    def row_degrees(self, lo: int, hi: int) -> np.ndarray:
        """Per-row nnz of padded rows [lo, hi) WITHOUT materializing
        the slice — the remote-shard metadata of the per-host build
        (O(rows) indptr reads; for a memmapped triplet only that range
        of indptr pages in)."""
        if self._csr is not None:
            return np.diff(self._csr.indptr[lo:hi + 1]).astype(np.int64)
        _, _, indptr = self._trip
        out = np.zeros(hi - lo, dtype=np.int64)
        top = min(hi, self.n)
        if top > lo:
            seg = np.asarray(indptr[lo:top + 1], dtype=np.int64)
            out[:top - lo] = np.diff(seg)
        return out

    def rows(self, lo: int, hi: int) -> sparse.csr_matrix:
        """Canonical CSR of padded rows [lo, hi) x [0, total)."""
        if self._csr is not None:
            return self._csr[lo:hi]
        from arrow_matrix_tpu.io.graphio import csr_row_range

        out = csr_row_range(self._trip, lo, hi, self.total)
        nnz0 = out.nnz
        out.sum_duplicates()
        out.sort_indices()
        if out.nnz != nnz0:
            raise ValueError(
                f"triplet rows [{lo}, {hi}) contain duplicate entries; "
                f"binary detection runs on raw values, so duplicates "
                f"would silently diverge from the canonical matrix — "
                f"canonicalize the artifact first")
        return out


def _banded_reach(src: _SliceSource, w: int,
                  shard_ids=None) -> int:
    """Raw halo reach in ROWS: how far body columns stray outside the
    owning shard (head-arm columns excluded).  A converged
    block-diagonal level has reach 0 and pays no exchange; a grown
    banded last level gets exactly the hops it needs (reference
    neighbor exchange generalized, arrow_mpi.py:123-175).  Streams one
    device row-slice at a time (O(slice nnz) host memory).

    ``shard_ids`` restricts the scan (the per-host build scans only
    local shards and cross-process-maxes the result — per-host IO
    stays O(local nnz) end to end)."""
    L, n_dev = src.shard_len, src.n_dev
    reach = 0
    for d in (range(n_dev) if shard_ids is None else sorted(shard_ids)):
        lo = d * L
        coo = src.rows(lo, lo + L).tocoo()
        rows_g = coo.row + lo
        g = coo.col
        outside = (rows_g >= w) & (g >= w) & ((g < lo) | (g >= lo + L))
        if outside.any():
            go = g[outside]
            reach = max(reach,
                        int(np.maximum(lo - go, go - (lo + L) + 1).max()))
    return reach


def _hops_rem(reach: int, L: int, n_dev: int) -> tuple[int, int]:
    """(hops, rem) from a raw row reach: ``hops`` whole-shard exchange
    steps, of which the FARTHEST carries only ``rem`` <= L rows — the
    exact rows the halo region can reference (sublane-aligned).  A
    banded level with reach << L then ppermutes L/rem-times fewer
    bytes than a whole-shard chain; reach beyond the device ring caps
    at full shards."""
    if reach <= 0:
        return 0, 0
    hops_raw = -(-reach // L)
    hops = min(hops_raw, n_dev - 1)
    if hops_raw > n_dev - 1 or hops == 0:
        return hops, L if hops else 0
    rem = reach - (hops - 1) * L
    rem = min(align_up(rem, SLOT_ALIGN), L)
    return hops, rem


class _DegreesOnly:
    """Row-degree stand-in for a REMOTE device's body share (per-host
    multi-process build): enough for the global tier shapes/orderings
    (which every process must agree on), no entry data.  For a
    canonical source a body-share row's degree equals its full row nnz
    — every entry lands in exactly one category or the OWNING process
    raises — so the stand-in derives from indptr alone."""

    __slots__ = ("indptr",)
    indices = None      # the pack fill skips shares without entry data

    def __init__(self, degrees: np.ndarray):
        self.indptr = np.concatenate(
            [[0], np.cumsum(degrees, dtype=np.int64)])

    @property
    def nnz(self) -> int:
        return int(self.indptr[-1])


def _slim_shares(src: _SliceSource, w: int, hops: int,
                 materialize: Optional[set] = None) -> tuple[list, list]:
    """Per-device (body, head) shares via prioritized column
    categorization (COO): local shard > head arm > halos; anything
    matching no category is out of pattern and raises.  Body share
    columns: [0, L) local, [L, L+w) head arm, then the lo/hi halo
    regions of width hops*L each.  Streams one device row-slice at a
    time; the head block (w rows) materializes once.

    ``materialize`` (default: every shard) lists the device indices
    whose body shares carry entry data; the rest become
    :class:`_DegreesOnly` stand-ins — the per-host build, where each
    process constructs and validates only its own shards' shares (the
    reference's per-rank slice loading, spmm_petsc.py:421-440) and the
    remote slots of the device stacks stay untouched zero pages.  Head
    shares always materialize (w rows, column-sliced — cheap, and the
    head operator is replicated work anyway)."""
    L, n_dev = src.shard_len, src.n_dev
    H = hops * L
    head_block = src.rows(0, w)
    body_shares, head_shares = [], []
    for d in range(n_dev):
        lo, hi = d * L, (d + 1) * L
        head_shares.append(head_block[:, lo:hi].tocsr())
        if materialize is not None and d not in materialize:
            degrees = src.row_degrees(lo, hi)
            if d == 0:
                degrees = degrees.copy()
                degrees[:w] = 0          # head rows live in the head op
            body_shares.append(_DegreesOnly(degrees))
            continue
        rows = src.rows(lo, hi).tocoo()
        r, g, v = rows.row, rows.col, rows.data
        if d == 0:
            # global head rows: the head operator covers them.
            keep = (r + lo) >= w
            r, g, v = r[keep], g[keep], v[keep]
        local = (g >= lo) & (g < hi)
        head_arm = ~local & (g < w)
        lo_h = ~local & ~head_arm & (g >= lo - H) & (g < lo)
        hi_h = ~local & ~head_arm & (g >= hi) & (g < hi + H)
        cat = local | head_arm | lo_h | hi_h
        if not cat.all():
            raise ValueError(
                f"shard {d} has {int((~cat).sum())} nonzeros outside "
                f"the slim pattern at width {w} / {hops}-hop halos "
                f"(head rows/arm + shard +- reach)")
        mapped = np.where(
            local, g - lo,
            np.where(head_arm, L + g,
                     np.where(lo_h, L + w + (g - (lo - H)),
                              L + w + H + (g - hi))))
        share = sparse.csr_matrix(
            (v, (r, mapped)), shape=(L, L + w + 2 * H))
        share.sum_duplicates()
        share.sort_indices()
        body_shares.append(share)
    return body_shares, head_shares


def _carried_maps(perm: np.ndarray, body_order: np.ndarray, L: int,
                  total: int) -> tuple[np.ndarray, np.ndarray]:
    """Carried-position <-> original-row maps for one level's tiered
    ordering.  Position p (device d, tiered slot) holds level row
    r = d*L + body_order[d, slot], i.e. original row perm[r]; -1 slots
    are tier padding.  Returns (orig_of_pos (T,), pos_of_orig (total,)),
    both -1 where undefined.  Shared by SellMultiLevel and
    SellSpaceShared."""
    n_dev, rows_out = body_order.shape
    # int32: rows and positions stay far below 2^31 even at the 2^26
    # scale rung — these maps are the largest host-resident metadata
    # of a multi-level build (2 per level at O(total)).  Guarded: a
    # silent wrap would corrupt every route (fail loudly at build
    # time, the routing.py convention).
    if max(total, rows_out * n_dev) >= 2**31:
        raise ValueError(
            f"carried maps exceed int32 range "
            f"(total={total}, positions={rows_out * n_dev})")
    oop = np.full(rows_out * n_dev, -1, dtype=np.int32)
    for d in range(n_dev):
        src = body_order[d]
        live = src >= 0
        oop[d * rows_out + np.flatnonzero(live)] = perm[
            d * L + src[live]]
    poo = np.full(total, -1, dtype=np.int32)
    live = oop >= 0
    poo[oop[live]] = np.flatnonzero(live)
    return oop, poo


def _live(oop: np.ndarray, n: int) -> np.ndarray:
    """Positions of a carried ordering that hold a real original row
    (< n): THE pad-sentinel definition — scatter, gather, and the
    reduction masks must all agree on it."""
    return (oop >= 0) & (oop < n)


def _scatter_carried(x: np.ndarray, oop: np.ndarray, n: int) -> np.ndarray:
    """Host (n, k) original-order features -> (T, k) carried ordering
    (tier padding and rows past n stay zero)."""
    feat = np.zeros((oop.size, x.shape[1]), dtype=x.dtype)
    live = _live(oop, n)
    feat[live] = x[oop[live]]
    return feat


def _gather_carried(c: np.ndarray, oop: np.ndarray, n: int) -> np.ndarray:
    """(T, k) carried-order result -> host (n, k) original order."""
    out = np.zeros((n, c.shape[-1]), dtype=c.dtype)
    live = _live(oop, n)
    out[oop[live]] = c[live]
    return out


def _positions_inv(body_order: np.ndarray, L: int) -> np.ndarray:
    """inv[d, r] = tiered position of share row r on share d."""
    n_shares = body_order.shape[0]
    inv = np.zeros((n_shares, L), dtype=np.int64)
    for d in range(n_shares):
        live = body_order[d] >= 0
        inv[d, body_order[d][live]] = np.flatnonzero(live)
    return inv


def _local_operand_width(rows_out: int, w: int, hops: int, L: int) -> int:
    """Width of the z operand one device's tiered SpMM gathers from:
    [tiered rows | head arm w | lo halos hops*L | hi halos hops*L] —
    must mirror _slim_shares' share width (L + w + 2H) after the
    local-part remap to rows_out, and _slim_local_step's z concat.
    The ONE bound the int16 index decision keys on."""
    return rows_out + w + 2 * hops * L


def _remap_body_cols(body: SellShardStack, inv: np.ndarray, L: int,
                     rows_out: int, w: int, hops: int,
                     materialize: Optional[set] = None) -> SellShardStack:
    """Body column remap: share column c ->
      [0, L): local -> tiered position;   [L, L+w): head -> R + (c-L)
      [L+w, L+w+H): lo halo;              [L+w+H, L+w+2H): hi halo
    (halo regions pass through at the same offsets past R).
    Indices narrow to int16 whenever the local operand width fits
    (half the streamed index bytes — the block_index_dtype rule of the
    stacked formats, ops/ell.py)."""
    R = rows_out
    idx_dtype = block_index_dtype(_local_operand_width(rows_out, w,
                                                       hops, L))
    remapped = []
    for cols in body.cols:
        c = np.asarray(cols)
        # np.zeros, not empty: remote shards of the per-host build are
        # skipped below and their slices must stay untouched (virtual)
        # zero pages, not garbage indices.
        out = np.zeros(c.shape, dtype=idx_dtype)
        for d in range(c.shape[0]):
            if materialize is not None and d not in materialize:
                continue
            cd = c[d].astype(np.int64)
            local = inv[d, np.minimum(cd, L - 1)]
            out[d] = np.where(cd < L, local, R + (cd - L)).astype(idx_dtype)
        remapped.append(out)
    return body.replace(cols=tuple(remapped))


def _remap_head_cols(head: SellShardStack, inv: np.ndarray, L: int,
                     rows_out: int,
                     materialize: Optional[set] = None) -> SellShardStack:
    idx_dtype = block_index_dtype(rows_out)
    remapped_head = []
    for cols in head.cols:
        c = np.asarray(cols)
        out = np.zeros(c.shape, dtype=idx_dtype)
        for d in range(c.shape[0]):
            if materialize is not None and d not in materialize:
                continue
            out[d] = inv[d, np.minimum(c[d], L - 1)].astype(idx_dtype)
        remapped_head.append(out)
    return head.replace(cols=tuple(remapped_head))


def local_shard_coords(mesh: Mesh, *axes: str):
    """The multi-process build probe shared by build_slim_level and
    SellSpaceShared: None when every mesh device is process-local
    (single-process — materialize everything); otherwise the set of
    this process's device coordinates along ``axes`` (1-tuples unpack
    to ints)."""
    if all(d.process_index == jax.process_index()
           for d in mesh.devices.flat):
        return None
    ax = [list(mesh.axis_names).index(a) for a in axes]
    coords = {
        tuple(int(c[i]) for i in ax)
        for c, dev in np.ndenumerate(mesh.devices)
        if dev.process_index == jax.process_index()}
    return ({c[0] for c in coords} if len(axes) == 1 else coords)


def global_max_reach(reach: int) -> int:
    """Cross-process max of a locally-scanned halo reach (in ROWS) —
    every process must agree on the operand shapes it implies (the one
    collective in a per-host build)."""
    from jax.experimental import multihost_utils

    return int(np.max(multihost_utils.process_allgather(
        np.asarray(reach, dtype=np.int32))))


def build_slim_level(matrix: CsrLike, width: int, mesh: Mesh,
                     axis: str, dtype, binary: bool,
                     shard_len: Optional[int] = None,
                     ladder=None) -> SlimLevelOps:
    """Build one level's per-device SELL operators (see module
    docstring).  Captures the banded slim pattern: body columns may
    fall in the shard, the head arm [0, w), or the two w-wide halo
    regions at the shard edges (exchanged by ppermute at runtime).
    ``matrix`` may be a CSR, a (memmapped) npy triplet, or an
    already-built ``_SliceSource`` — triplet builds stream one device
    slice at a time and never materialize the matrix."""
    n_dev = mesh.shape[axis]
    w = width
    src = (matrix if isinstance(matrix, _SliceSource)
           else _SliceSource(matrix, n_dev, w, shard_len=shard_len))
    L = src.shard_len

    # Per-host build: when the mesh spans processes, scan, construct
    # and validate only THIS process's shards (the global tier shapes/
    # orderings come from degree metadata, identical on every
    # process); remote slices of the device stacks stay untouched zero
    # pages that put_global never reads.
    materialize = local_shard_coords(mesh, axis)
    reach = _banded_reach(src, w, shard_ids=materialize)
    if materialize is not None:
        reach = global_max_reach(reach)
    hops, rem = _hops_rem(reach, L, n_dev)
    body_shares, head_shares = _slim_shares(src, w, hops,
                                            materialize=materialize)

    growth, align = resolve_ladder(ladder)
    ladder_body = degree_ladder(
        max((int(np.diff(s.indptr).max()) if s.nnz else 0)
            for s in body_shares), growth, align)
    # Global head degrees from the shares (their columns partition
    # [0, total)) — no second head-block read on the streamed path.
    head_glob_deg = sum(np.diff(h.indptr) for h in head_shares)
    ladder_head = degree_ladder(
        int(head_glob_deg.max()) if head_glob_deg.size else 0,
        growth, align)

    body, body_order, rows_out = _pack_shard_tiers(
        body_shares, ladder_body, binary, dtype)
    head, head_order, _ = _pack_shard_tiers(
        head_shares, ladder_head, binary, dtype,
        shared_degrees=head_glob_deg)

    if not np.array_equal(body_order[0, :w], np.arange(w)):
        raise AssertionError(
            "device 0's head rows must lead its tiered ordering "
            "(stable zero-tier sort invariant)")

    inv = _positions_inv(body_order, L)
    body = _remap_body_cols(body, inv, L, rows_out, w, hops,
                            materialize=materialize)
    head = _remap_head_cols(head, inv, L, rows_out,
                            materialize=materialize)

    if not np.all(head_order[0] == head_order):
        raise AssertionError("head tier ordering must be "
                             "device-independent")
    head_unsort = np.argsort(head_order[0][:w])[:w].astype(np.int32)

    shard_stack = NamedSharding(mesh, P(axis))
    repl = NamedSharding(mesh, P())
    body = jax.tree_util.tree_map(
        lambda arr: put_global(arr, shard_stack), body)
    head = jax.tree_util.tree_map(
        lambda arr: put_global(arr, shard_stack), head)
    return SlimLevelOps(
        body=body, head=head,
        head_unsort=put_global(head_unsort, repl),
        orig_pos=put_global(inv.astype(np.int32), shard_stack),
        body_order=body_order, rows_out=rows_out, shard_len=L,
        n_dev=n_dev, width=w, hops=hops, rem=rem, binary=binary)


def _slim_local_step(axis: str, w: int, rows_out: int, hops: int,
                     rem: int, n_dev: int, gather_budget, body, head,
                     head_unsort, orig_pos, xt):
    """One device's slim step body, shared by the time-shared
    (make_sharded_step) and space-shared (sell_space) orchestrations —
    masked-psum X_0 broadcast, halo ppermute chains, tiered SpMM, head
    psum + device-0 overwrite (tier gathers bounded by
    ``gather_budget``).  All collectives name only ``axis``, so
    under a 2-D (lvl, blocks) shard_map they stay within each level
    group by construction.  ``head_unsort``: (w,) tiered head position
    of each head row, already resolved by the caller."""
    dev = lax.axis_index(axis)
    with jax.named_scope("bcast_head"):
        x0 = lax.psum(
            jnp.where(dev == 0, xt[:, :w], jnp.zeros_like(xt[:, :w])),
            axis)
    parts = [xt, x0]
    if hops:
        # Halo chains: my rows in ORIGINAL shard order, shifted j hops
        # right feed the lo region, j hops left the hi region.
        # ppermute leaves chain ends zero — the boundary condition
        # (reference arrow_mpi.py:150-162).  Intermediate hops relay
        # whole shards (those regions sit entirely within reach), but
        # the FARTHEST hop carries only the ``rem`` rows the region
        # can reference — a reach << L band ppermutes L/rem-times
        # fewer bytes; the skipped rows are zero by the reach
        # definition, so zero-padding the received slice is exact.
        with jax.named_scope("halo_exchange"):
            mine = jnp.take(xt, orig_pos[0], axis=1)     # (k, L)
            Ls = mine.shape[1]
            fwd = [(i, i + 1) for i in range(n_dev - 1)]
            bwd = [(i + 1, i) for i in range(n_dev - 1)]
            lo_chain, hi_chain = [], []
            cur_lo = cur_hi = mine
            # rem == 0 means whole-shard (the pre-slicing behavior): a
            # caller that never derived rem still gets a correct step.
            rem_eff = rem if rem > 0 else Ls
            for j in range(hops):
                if j == hops - 1 and rem_eff < Ls:
                    got_lo = lax.ppermute(cur_lo[:, Ls - rem_eff:], axis,
                                          perm=fwd)
                    got_hi = lax.ppermute(cur_hi[:, :rem_eff], axis,
                                          perm=bwd)
                    zpad = jnp.zeros((mine.shape[0], Ls - rem_eff),
                                     mine.dtype)
                    lo_chain.append(jnp.concatenate([zpad, got_lo],
                                                    axis=1))
                    hi_chain.append(jnp.concatenate([got_hi, zpad],
                                                    axis=1))
                else:
                    cur_lo = lax.ppermute(cur_lo, axis, perm=fwd)
                    cur_hi = lax.ppermute(cur_hi, axis, perm=bwd)
                    lo_chain.append(cur_lo)   # j hops left neighbor
                    hi_chain.append(cur_hi)   # j hops right neighbor
            # lo region covers [lo - hops*L, lo): farthest first.
            parts += list(reversed(lo_chain)) + hi_chain
    with jax.named_scope("body_spmm"):
        z = jnp.concatenate(parts, axis=1)
        out = _stack_spmm_t(body, z, gather_budget)  # (k, rows_out)
    with jax.named_scope("head_reduce"):
        head_part = _stack_spmm_t(head, xt, gather_budget)
        c0 = lax.psum(head_part, axis)
        c0w = jnp.take(c0, head_unsort, axis=1)[:, :w]
        out = jnp.where(
            (dev == 0) & (jnp.arange(rows_out)[None, :] < w),
            jnp.pad(c0w, ((0, 0), (0, rows_out - w))), out)
    return out


def make_sharded_step(mesh: Mesh, axis: str, width: int, rows_out: int,
                      hops: int = 0, rem: int = 0,
                      feat_axis: Optional[str] = None,
                      gather_budget: Optional[int] = None):
    """Raw (traceable) shard_map'd slim step for one level:
    ``step(body, head, head_unsort, orig_pos, xt) -> ct`` on
    feature-major (k, total_out) arrays.

    ``hops`` whole-shard ppermute chains feed the halo regions (0 for
    converged block-diagonal levels — no exchange at all; a grown
    banded level gets exactly the reach it needs).  ``feat_axis``
    additionally shards the feature rows (axis 0) — the k-dimension
    tiling axis (reference GPU feature blocking): the per-level
    compute and collectives never mix feature rows, so the extra axis
    composes transparently."""
    w = width
    n_dev = mesh.shape[axis]

    def local_step(body, head, head_unsort, orig_pos, xt):
        return _slim_local_step(axis, w, rows_out, hops, rem, n_dev,
                                gather_budget, body, head, head_unsort,
                                orig_pos, xt)

    spec = lambda tree: jax.tree_util.tree_map(lambda _: P(axis), tree)

    x_spec = P(feat_axis, axis)

    def step(body, head, head_unsort, orig_pos, xt):
        return shard_map(
            local_step, mesh=mesh,
            in_specs=(spec(body), spec(head), P(), P(axis), x_spec),
            out_specs=x_spec,
            check_vma=False,
        )(body, head, head_unsort, orig_pos, xt)

    return step


def _overlap_step(step, overlap_slabs: int, xt_pos: int = -1):
    """Chunked overlap schedule (graft-stream): wrap a feature-major
    step so the carried (k, total) array is split into S static
    sub-slabs along the feature axis, each running the full step —
    halo ppermutes / routed all_to_alls for slab i+1 are dataflow-
    independent of slab i's SELL compute, so XLA's latency-hiding
    scheduler can dispatch the next exchange while the current slab
    computes.  f32 results are bit-identical to the unsplit step: the
    split never regroups any output element's addends.  ``S`` is
    trace-time static (audited by the recompile gate); ``xt_pos``
    locates the carried array in the step's signature."""
    if overlap_slabs <= 1:
        return step
    from arrow_matrix_tpu.parallel.routing import overlap_slices

    def wrapped(*args):
        args = list(args)
        pos = xt_pos if xt_pos >= 0 else len(args) + xt_pos
        xt = args[pos]
        outs = []
        for j, (lo, hi) in enumerate(
                overlap_slices(xt.shape[0], overlap_slabs)):
            with jax.named_scope(f"overlap_slab_{j}"):
                sub = list(args)
                sub[pos] = lax.slice_in_dim(xt, lo, hi, axis=0)
                outs.append(step(*sub))
        return jnp.concatenate(outs, axis=0)

    return wrapped


def _resolve_repl(mesh: Mesh, axis: str, repl_axis: Optional[str],
                  feat_axis: Optional[str] = None) -> int:
    """Validate a 2.5D replica axis request and return its factor c
    (1 when ``repl_axis is None``)."""
    if repl_axis is None:
        return 1
    if repl_axis not in mesh.axis_names:
        raise ValueError(
            f"repl_axis={repl_axis!r} is not a mesh axis "
            f"{tuple(mesh.axis_names)}; build the 2-D mesh with "
            f"make_repl_mesh(n_dev, c)")
    if repl_axis == axis:
        raise ValueError(
            f"repl_axis={repl_axis!r} must differ from the block "
            f"axis {axis!r}")
    if feat_axis is not None:
        raise ValueError(
            "repl_axis composes with feat_axis=None: the k-tiling "
            "axis already shards the feature rows across devices; "
            "the replica groups split them across exchange rounds")
    return int(mesh.shape[repl_axis])


def _repl_step(step, mesh: Mesh, axis: str, repl_axis: str,
               xt_pos: int = -1):
    """2.5D replicated schedule (graft-repl): wrap a feature-major
    step so each replica group runs it on only the static feature
    slab it owns (k/c rows), then scatters the result back into a
    full-k partial carriage (zeros outside the owned slab).  Every
    collective inside the step names only the block axis, so it runs
    within the replica group on a 1/c-width payload; SpMM is
    column-separable, so the partial carriage is closed under
    iteration and the masked ``psum`` merging the replicas is
    deferred to gather time (``routing.repl_merge_t``) — its cost is
    the 2.5D scheme's ``reduce_bytes``, paid once per gather rather
    than per step."""
    from arrow_matrix_tpu.parallel.routing import (
        repl_slab_scatter_t,
        repl_slab_take_t,
    )

    def wrapped(*args):
        args = list(args)
        pos = xt_pos if xt_pos >= 0 else len(args) + xt_pos
        xt = args[pos]
        k = xt.shape[0]
        with jax.named_scope("repl_slab_take"):
            args[pos] = repl_slab_take_t(xt, mesh, axis, repl_axis)
        out = step(*args)
        with jax.named_scope("repl_slab_scatter"):
            return repl_slab_scatter_t(out, k, mesh, axis, repl_axis)

    return wrapped


class SellSlim:
    """One arrow matrix distributed over a mesh axis in padding-free
    layouts (see module docstring).  API mirrors the other layouts:
    ``set_features`` / ``spmm`` / ``gather_result``.
    """

    def __init__(self, matrix: CsrLike, width: int, mesh: Mesh,
                 axis: str = "blocks", dtype=np.float32,
                 binary="auto", feature_dtype=None, ladder=None,
                 overlap_slabs: int = 1,
                 repl_axis: Optional[str] = None,
                 plan=None, plan_k: Optional[int] = None):
        # graft-tune consumption: the plan's structural knobs map onto
        # this executor's vocabulary — tier split -> ladder, carriage
        # dtype.  (repl stays mesh-determined via repl_axis, overlap S
        # is this executor's own argument; the fused kernel knobs are
        # fold-path-only.)  A single matrix
        # has no levels to hash, so plan='auto' is a loud error here —
        # pass a TunePlan/dict, or use SellMultiLevel/MultiLevelArrow.
        self.tune_plan = None
        if plan is not None:
            from arrow_matrix_tpu.tune.plan import resolve_plan

            resolved = resolve_plan(plan, plan_k=plan_k)
            if resolved is not None:
                self.tune_plan = resolved
                ladder = (resolved.fold_growth,
                          SLOT_ALIGN if resolved.fold_align is None
                          else resolved.fold_align)
                feature_dtype = resolved.feature_dtype
        # The source canonicalizes (in-memory CSR up front, memmapped
        # triplets per slice): binary detection must see canonical
        # values — duplicate all-ones entries sum to non-unit weights
        # and must go weighted (triplet slices reject duplicates).
        self.repl_axis = repl_axis
        self.repl = _resolve_repl(mesh, axis, repl_axis)
        src = _SliceSource(matrix, mesh.shape[axis], width)
        is_binary = src.resolve_binary(binary)
        self.feature_dtype = resolve_feature_dtype(feature_dtype)
        if self.feature_dtype is not None and \
                np.dtype(self.feature_dtype) == np.dtype(np.int8):
            raise ValueError(
                "int8 carriage is a fold-path capability (its (q, "
                "scale) carry pair has no sharded exchange story yet); "
                "the mesh executors carry f32 or bf16")
        self.n = src.n
        self.binary = is_binary
        self.mesh = mesh
        self.axis = axis
        self.width = width
        ops = build_slim_level(src, width, mesh, axis, dtype, is_binary,
                               ladder=ladder)
        self.ops = ops
        self.body, self.head = ops.body, ops.head
        self.body_order = ops.body_order
        self.rows_out, self.shard_len = ops.rows_out, ops.shard_len
        self.n_dev = ops.n_dev
        self.total_out = ops.total_out
        # Single-matrix carriage = the identity-permutation case of the
        # multi-level carried maps.
        self._oop, _ = _carried_maps(
            np.arange(self.shard_len * self.n_dev), ops.body_order,
            self.shard_len, self.shard_len * self.n_dev)
        self.overlap_slabs = int(overlap_slabs)
        self.gather_budget = mesh_gather_budget(mesh)
        raw_step = make_sharded_step(mesh, axis, width, ops.rows_out,
                                     hops=ops.hops, rem=ops.rem,
                                     gather_budget=self.gather_budget)
        # Wrapper order: repl outermost, overlap inside — each replica
        # group overlap-schedules its own k/c slab (S must divide k/c).
        step_sched = _overlap_step(raw_step, self.overlap_slabs)
        if self.repl > 1:
            step_sched = _repl_step(step_sched, mesh, axis, repl_axis)
        self._step = jax.jit(step_sched)
        if self.repl > 1:
            from arrow_matrix_tpu.parallel.routing import repl_merge_t

            self._merge = jax.jit(functools.partial(
                repl_merge_t, mesh=mesh, axis=axis,
                repl_axis=repl_axis))
        else:
            self._merge = lambda ct: ct

    def _feature_sharding(self):
        return NamedSharding(self.mesh, P(None, self.axis))

    def set_features(self, x: np.ndarray) -> jax.Array:
        """Host (n, k) -> feature-major (k, total_out) sharded array in
        the carried (per-shard tier-grouped) ordering."""
        n, k = x.shape
        if n != self.n:
            raise ValueError(f"expected {self.n} rows, got {n}")
        feat = _scatter_carried(x, self._oop, n)
        if self.feature_dtype is not None:
            feat = feat.astype(self.feature_dtype)
        return put_global(np.ascontiguousarray(feat.T),
                          self._feature_sharding())

    def spmm(self, xt: jax.Array) -> jax.Array:
        """One distributed SpMM step; feature-major in and out (iterate
        by feeding the result back)."""
        o = self.ops
        return self._step(o.body, o.head, o.head_unsort, o.orig_pos, xt)

    def gather_result(self, ct: jax.Array) -> np.ndarray:
        """Device (k, total_out) -> host (n, k) in original row order.
        With ``repl_axis`` the carriage is per-replica partial, so the
        masked psum merge over the replica axis runs first
        (``fetch_replicated`` assumes a truly replicated array)."""
        return _gather_carried(
            fetch_replicated(self._merge(ct)).astype(
                np.float32, copy=False).T,
            self._oop, self.n)

    def merge_carries(self, ct: jax.Array) -> jax.Array:
        """Canonical (fully replicated) form of the carried state: the
        2.5D masked-psum merge over the replica axis when ``repl > 1``,
        identity otherwise.  The merged carriage is a valid bit-exact
        resume state (the step re-extracts each replica's own slab), so
        checkpoints MUST save this form — ``utils/checkpoint``'s host
        path calls ``fetch_replicated``, which would silently drop the
        other replicas' slabs from a divergent carriage."""
        return self._merge(ct)

    def ideal_comm_bytes(self, k: int, itemsize: int = 4) -> int:
        """Paper cost model for one slim step at feature width ``k``:
        the arrow bound is O(width) rows exchanged per device — the
        head-partial reduction every non-root device contributes
        (paper Thm: communication O(n_dev * width) per iteration,
        independent of n).  Under 2.5D replication each replica
        group's exchanges carry a k/c feature slab, so the per-device
        ideal scales by 1/c (n_dev is already the per-group block
        count on a repl mesh)."""
        return (max(self.n_dev - 1, 0) * self.width
                * (k // max(self.repl, 1)) * itemsize)

    def reduce_comm_bytes(self, k: int, itemsize: int = 4) -> int:
        """Per-device bytes of the 2.5D final reduction (the masked
        psum over the replica axis at gather time); 0 when repl==1.
        Reported as the comm account's ``reduce_bytes`` — the once-
        per-gather price of cutting every per-step exchange by c."""
        if self.repl <= 1:
            return 0
        return self.rows_out * k * itemsize

    def collective_contract(self, k: int, itemsize: int = None):
        """Static communication promise for graft-prove (analysis/
        contracts.py): the slim step's only exchange is the head-partial
        psum (all-reduce) over the block axis, carrying the k/(c·S)
        feature slab; the measured/ideal band covers the HLO accountant
        counting per-device padded output shapes against the paper's
        logical O(width) row bound.  ``itemsize`` defaults to the
        carried feature dtype's (graft-classes: an approx-carriage
        contract promises proportionally fewer ideal bytes)."""
        from arrow_matrix_tpu.analysis.contracts import CollectiveContract

        if itemsize is None:
            itemsize = np.dtype(self.feature_dtype or np.float32).itemsize
        return CollectiveContract(
            algorithm="sell_slim",
            step_bytes=self.ideal_comm_bytes(k, itemsize),
            reduce_bytes=self.reduce_comm_bytes(k, itemsize),
            repl=self.repl,
            overlap_slabs=self.overlap_slabs,
            dtype=np.dtype(self.feature_dtype or np.float32).name
            .replace("float", "f").replace("bfloat", "bf"),
            lowered_kinds=("all-reduce",),
            compiled_kinds=("all-reduce",),
            ratio_band=(0.25, 4.0),
            notes="HLO counts the psum's padded (slab, rows_out) "
                  "output per device; the ideal counts (n_dev-1)*width "
                  "logical rows")

    def predicted_hbm_bytes(self, k: int, itemsize: int = 4,
                            repl: int = 1) -> int:
        """Static per-shard HBM model for one slim step at feature
        width ``k``: this device's slice of the tier stacks (every
        stack carries a leading device axis) plus the carried feature
        input and output (rows_out positions each).  obs/memview
        judges the compiled executable against this.

        ``repl`` is the PLANNING multiplier for the 2.5D scheme: at
        replication c both the operator slice and the carriage per
        device grow exactly ×c (c-fold coarser block shards).  An
        executor already built on a repl mesh bakes its own ×c into
        the base (n_dev is the per-group block count) — keep the
        default ``repl=1`` when judging it."""
        base = (self.ops.device_nbytes() // self.n_dev
                + 2 * self.rows_out * k * itemsize)
        return base * max(int(repl), 1)

    def shard_report(self) -> dict:
        """Per-device load report from the packed tier metadata
        (obs/imbalance.py schema)."""
        from arrow_matrix_tpu.obs.imbalance import summarize_units

        b_nnz, b_slots = self.body.shard_stats()
        h_nnz, h_slots = self.head.shard_stats()
        rows = np.full(self.n_dev, self.rows_out, dtype=np.int64)
        return summarize_units(rows, b_nnz + h_nnz, b_slots + h_slots,
                               units="device")


class SellMultiLevel:
    """K decomposition levels on the padding-free layouts: per-level
    SellSlim compute chained by composed reordering gathers (the
    feature-major counterpart of ``MultiLevelArrow`` on a mesh).

    Semantics match MultiLevelArrow.step (reference
    arrow_dec_mpi.py:283-307): X carried in level-0's tiered ordering;
    forward gathers re-order it into each level's ordering, every level
    runs the slim step, partial results aggregate backward.  The
    inter-level gathers are left to the GSPMD partitioner (the
    ``routing="gather"`` lowering); their indices compose the level
    permutations AND the per-shard tier orderings, so the tier sorts
    stay free.
    """

    def __init__(self, levels, width: int, mesh: Mesh,
                 axis: str = "blocks", dtype=np.float32, binary="auto",
                 routing: str = "a2a",
                 feat_axis: Optional[str] = None, feature_dtype=None,
                 ladder=None, overlap_slabs: int = 1,
                 repl_axis: Optional[str] = None,
                 plan=None, plan_k: Optional[int] = None):
        """``routing``: "a2a" (default) compiles the inter-level
        reorderings into explicit per-device send/recv tables over one
        fixed-shape all_to_all each (parallel/routing.py — tier-padding
        positions route from the local dummy and cost no cross-device
        slots; measured lowest comm volume AND fastest wall-clock of
        every execution mode); "gather" leaves them to the GSPMD
        partitioner (may all-gather — kept for comparison).
        ``feat_axis`` (the k-tiling axis) composes with either routing:
        the a2a tables are per-device and feature-row-independent, so
        each feature slice runs its own identical exchange."""
        from arrow_matrix_tpu.parallel.multi_level import pad_permutation

        # graft-tune consumption (see SellSlim): with the full levels
        # in hand this executor supports plan="auto" — hash the
        # structure, look the cached plan up, fall back LOUDLY on miss.
        self.tune_plan = None
        if plan is not None:
            from arrow_matrix_tpu.tune.plan import resolve_plan

            resolved = resolve_plan(plan, levels=levels, width=width,
                                    dtype=dtype, binary=binary,
                                    plan_k=plan_k)
            if resolved is not None:
                self.tune_plan = resolved
                ladder = (resolved.fold_growth,
                          SLOT_ALIGN if resolved.fold_align is None
                          else resolved.fold_align)
                feature_dtype = resolved.feature_dtype

        if routing not in ("gather", "a2a"):
            raise ValueError(f"unknown routing {routing!r}")
        if overlap_slabs > 1 and feat_axis is not None:
            raise ValueError(
                "overlap_slabs composes with feat_axis=None: the "
                "k-tiling axis already splits the feature rows across "
                "devices; the overlap schedule splits them in time")

        self.overlap_slabs = int(overlap_slabs)
        self.routing = routing
        self.feat_axis = feat_axis
        self.repl_axis = repl_axis
        self.repl = _resolve_repl(mesh, axis, repl_axis,
                                  feat_axis=feat_axis)
        if self.repl > 1 and routing == "gather":
            raise ValueError(
                "repl_axis composes with routing='a2a': the GSPMD "
                "gather lowering treats the carried features as "
                "replicated, but the 2.5D slab carriage is divergent "
                "across replica groups (verified corrupt, not just "
                "reordered f32)")
        self.feature_dtype = resolve_feature_dtype(feature_dtype)
        if self.feature_dtype is not None and \
                np.dtype(self.feature_dtype) == np.dtype(np.int8):
            raise ValueError(
                "int8 carriage is a fold-path capability (its (q, "
                "scale) carry pair has no sharded exchange story yet); "
                "the mesh executors carry f32 or bf16")

        if not levels:
            raise ValueError("empty decomposition")
        self.mesh = mesh
        self.axis = axis
        self.width = width
        n_dev = mesh.shape[axis]
        self.n = num_rows(levels[0].matrix)
        shard_len = max(align_up(-(-self.n // n_dev), width), width)
        total = shard_len * n_dev
        # One streaming source per level: a memmapped-triplet
        # decomposition builds device share by device share without
        # materializing any level (VERDICT r1 item 4 for the
        # feature-major paths).
        srcs = [_SliceSource(lvl.matrix, n_dev, width,
                             shard_len=shard_len) for lvl in levels]
        if binary is False:
            self.binary = False
        else:
            self.binary = all(s.resolve_binary(binary) for s in srcs)
        self.ops: List[SlimLevelOps] = [
            build_slim_level(s, width, mesh, axis, dtype,
                             self.binary, shard_len=shard_len,
                             ladder=ladder)
            for s in srcs
        ]

        # Carried-position <-> original-row maps per level
        # (_carried_maps: perm composed with the tiered ordering),
        # built LAZILY two levels at a time below: live host metadata
        # stays O(2 levels), not O(K levels) — part of the streamed-
        # build RSS bound (PERFORMANCE.md scale ladder note).
        def maps_for(i: int):
            perm = pad_permutation(np.asarray(levels[i].permutation),
                                   total)
            return _carried_maps(perm, self.ops[i].body_order,
                                 shard_len, total)

        oop_cur, poo_cur = maps_for(0)
        self._orig_of_pos0 = oop_cur

        repl = NamedSharding(mesh, P())

        def route(dst_oop, src_poo, src_total_out):
            """positions of the destination ordering -> positions of the
            source ordering holding the same original row (tier-padding
            destinations carry no value: GSPMD mode points them at 0 —
            never consumed — and a2a mode routes them from the local
            dummy, coming out zero)."""
            idx = np.where(dst_oop >= 0,
                           src_poo[np.minimum(dst_oop, total - 1)], 0)
            idx = np.maximum(idx, 0)
            if routing == "a2a":
                from arrow_matrix_tpu.parallel.routing import (
                    build_route,
                    shard_route,
                )

                rt = build_route(idx, n_dev, src_total=src_total_out,
                                 pad_mask=dst_oop < 0)
                return shard_route(rt, mesh, axis)
            return put_global(idx.astype(np.int32), repl)

        k_levels = len(levels)
        self.fwd, self.bwd = [], []
        for i in range(1, k_levels):
            oop_next, poo_next = maps_for(i)
            self.fwd.append(route(oop_next, poo_cur,
                                  self.ops[i - 1].total_out))
            self.bwd.append(route(oop_cur, poo_next,
                                  self.ops[i].total_out))
            oop_cur, poo_cur = oop_next, poo_next

        # Paper cost model of the inter-level routing, in row-units
        # (k=1, itemsize=1): rows whose adjacent-level positions land
        # on different devices (commstats.ideal_routing_bytes, the
        # reference Alltoallv payload).  obs/comm scales this by the
        # feature width to judge the compiled collectives.
        from arrow_matrix_tpu.utils import commstats

        padded = [pad_permutation(np.asarray(lvl.permutation), total)
                  for lvl in levels]
        self._ideal_route_units = commstats.ideal_routing_bytes(
            padded, n_dev, 1, itemsize=1)

        self.gather_budget = mesh_gather_budget(mesh)
        steps = [make_sharded_step(mesh, axis, width, ops.rows_out,
                                   hops=ops.hops, rem=ops.rem,
                                   feat_axis=feat_axis,
                                   gather_budget=self.gather_budget)
                 for ops in self.ops]
        feat_shard = NamedSharding(mesh, P(feat_axis, axis))

        from arrow_matrix_tpu.parallel.routing import (
            RouteTables,
            routed_take_t,
        )

        def reorder(xt, table):
            if isinstance(table, RouteTables):
                return routed_take_t(xt, table, mesh, axis,
                                     feat_axis=feat_axis)
            return lax.with_sharding_constraint(
                jnp.take(xt, table, axis=1), feat_shard)

        def step_fn(xt, level_ops, fwd, bwd):
            x_cur = xt
            partials = []
            for i in range(k_levels):
                if i > 0:
                    with jax.named_scope(f"route_forward_{i}"):
                        x_cur = reorder(x_cur, fwd[i - 1])
                o = level_ops[i]
                with jax.named_scope(f"level_{i}_spmm"):
                    partials.append(steps[i](o.body, o.head,
                                             o.head_unsort,
                                             o.orig_pos, x_cur))
            with jax.named_scope("aggregate_backward"):
                agg = partials[-1]
                for i in range(k_levels - 1, 0, -1):
                    agg = partials[i - 1] + reorder(agg, bwd[i - 1])
            return agg

        # Levels as pytree args would be natural, but SlimLevelOps is a
        # plain dataclass; pass the arrays through a tuple-of-stacks
        # pytree instead.
        self._level_args = tuple(
            (o.body, o.head, o.head_unsort, o.orig_pos)
            for o in self.ops)

        def step_packed(xt, level_args, fwd, bwd):
            class _O:  # tiny adaptor so step_fn reads .body etc.
                __slots__ = ("body", "head", "head_unsort", "orig_pos")

                def __init__(self, t):
                    (self.body, self.head, self.head_unsort,
                     self.orig_pos) = t

            return step_fn(xt, [_O(t) for t in level_args], fwd, bwd)

        step_sched = _overlap_step(step_packed, self.overlap_slabs,
                                   xt_pos=0)
        # Repl outermost, overlap inside: each replica group runs the
        # whole forward/aggregate pipeline (routes included) on its
        # k/c feature slab, overlap-scheduled in S sub-slabs of that
        # slab (S must divide k/c).
        if self.repl > 1:
            step_sched = _repl_step(step_sched, mesh, axis, repl_axis,
                                    xt_pos=0)
        self._step = jax.jit(step_sched)
        if self.repl > 1:
            from arrow_matrix_tpu.parallel.routing import repl_merge_t

            self._merge = jax.jit(functools.partial(
                repl_merge_t, mesh=mesh, axis=axis,
                repl_axis=repl_axis))
        else:
            self._merge = lambda ct: ct

        def scan_steps(xt, level_args, fwd, bwd, n):
            def body(xc, _):
                return step_sched(xc, level_args, fwd, bwd), None

            out, _ = lax.scan(body, xt, None, length=n)
            return out

        self._scan = jax.jit(scan_steps, static_argnames=("n",))
        self._scan_donated = jax.jit(scan_steps, static_argnames=("n",),
                                     donate_argnums=(0,))

    def set_features(self, x: np.ndarray) -> jax.Array:
        """Host (n, k) original order -> (k, total_out_0) carried."""
        n, k = x.shape
        if n != self.n:
            raise ValueError(f"expected {self.n} rows, got {n}")
        feat = _scatter_carried(x, self._orig_of_pos0, n)
        if self.feature_dtype is not None:
            feat = feat.astype(self.feature_dtype)
        return put_global(
            np.ascontiguousarray(feat.T),
            NamedSharding(self.mesh, P(self.feat_axis, self.axis)))

    carries_feature_major = True

    @property
    def step_fn(self):
        """Jitted step callable (see MultiLevelArrow.step_fn)."""
        return self._step

    def step_operands(self):
        """Device operands of one step (see MultiLevelArrow
        .step_operands)."""
        return (self._level_args, self.fwd, self.bwd)

    def step(self, xt: jax.Array) -> jax.Array:
        from arrow_matrix_tpu.faults import on_step as _fault_hook

        xt = _fault_hook("sell_slim.step", xt)
        return self._step(xt, self._level_args, self.fwd, self.bwd)

    def run(self, xt: jax.Array, iterations: int,
            donate: bool = False) -> jax.Array:
        """``donate=True`` donates ``xt`` to the scan carry so the old
        feature buffer is reused instead of doubling the footprint
        (same contract as MultiLevelArrow.run; the donated input is
        invalid afterwards)."""
        fn = self._scan_donated if donate else self._scan
        return fn(xt, self._level_args, self.fwd, self.bwd,
                  n=iterations)

    def gather_result(self, ct: jax.Array) -> np.ndarray:
        """With ``repl_axis`` the carriage is per-replica partial, so
        the masked psum merge over the replica axis runs first
        (``fetch_replicated`` assumes a truly replicated array)."""
        return _gather_carried(
            fetch_replicated(self._merge(ct)).astype(
                np.float32, copy=False).T,
            self._orig_of_pos0, self.n)

    def merge_carries(self, ct: jax.Array) -> jax.Array:
        """Canonical (fully replicated) form of the carried state: the
        2.5D masked-psum merge over the replica axis when ``repl > 1``,
        identity otherwise.  The merged carriage is a valid bit-exact
        resume state (the step re-extracts each replica's own slab), so
        checkpoints MUST save this form — ``utils/checkpoint``'s host
        path calls ``fetch_replicated``, which would silently drop the
        other replicas' slabs from a divergent carriage."""
        return self._merge(ct)

    def ideal_comm_bytes(self, k: int, itemsize: int = 4) -> int:
        """Paper cost model for one multi-level step at feature width
        ``k``: inter-level permutation routing (only rows that change
        device, both directions) plus each level's O(width) head
        exchange — the bound the measured collective bytes are judged
        against.  Under 2.5D replication every exchange carries a k/c
        slab within its replica group, so the per-device ideal scales
        by 1/c (the route units were already built over the coarser
        per-group block count)."""
        n_dev = self.mesh.shape[self.axis]
        per_level_head = max(n_dev - 1, 0) * self.width
        return (self._ideal_route_units
                + len(self.ops) * per_level_head) \
            * (k // max(self.repl, 1)) * itemsize

    def reduce_comm_bytes(self, k: int, itemsize: int = 4) -> int:
        """Per-device bytes of the 2.5D final reduction (the masked
        psum over the replica axis at gather time); 0 when repl==1.
        Reported as the comm account's ``reduce_bytes`` — the once-
        per-gather price of cutting every per-step exchange by c."""
        if self.repl <= 1:
            return 0
        return self.ops[0].rows_out * k * itemsize

    def collective_contract(self, k: int, itemsize: int = None):
        """Static communication promise for graft-prove: the a2a
        routing tables exchange inter-level rows (all-to-all) and each
        level's head partials psum over the block axis (all-reduce),
        every collective carrying the k/(c·S) feature slab.  The scan
        entry point donates the carried features (flat param 0), so
        the prover additionally demands input-output aliasing (H5).
        ``itemsize`` defaults to the carried feature dtype's
        (graft-classes: a bf16 carriage halves the promised band)."""
        from arrow_matrix_tpu.analysis.contracts import CollectiveContract

        if itemsize is None:
            itemsize = np.dtype(self.feature_dtype or np.float32).itemsize
        return CollectiveContract(
            algorithm="sell_multi",
            step_bytes=self.ideal_comm_bytes(k, itemsize),
            reduce_bytes=self.reduce_comm_bytes(k, itemsize),
            repl=self.repl,
            overlap_slabs=self.overlap_slabs,
            dtype=np.dtype(self.feature_dtype or np.float32).name
            .replace("float", "f").replace("bfloat", "bf"),
            lowered_kinds=("all-to-all", "all-reduce"),
            compiled_kinds=("all-to-all", "all-reduce"),
            ratio_band=(0.25, 4.0),
            donated_params=(0,),
            # XLA's while-loop copy insertion lands one copy set per
            # loop body (outer iteration scan + per-level hop scans),
            # and the overlap schedule multiplies the bodies by S;
            # transposes stay forbidden.
            hot_copy_budget=16 * self.overlap_slabs,
            notes="a2a fixed-slot padding and per-level psum padding "
                  "sit above the moved-row ideal; the band absorbs "
                  "both")

    def predicted_hbm_bytes(self, k: int, itemsize: int = 4,
                            repl: int = 1) -> int:
        """Static per-shard HBM model for one multi-level step at
        feature width ``k``: this device's slice of every level's tier
        stacks and the inter-level route tables, plus the carried
        feature input and output (level-0 ordering).

        ``repl`` is the PLANNING multiplier for the 2.5D scheme: at
        replication c both the operator slice and the carriage per
        device grow exactly ×c (c-fold coarser block shards).  An
        executor already built on a repl mesh bakes its own ×c into
        the base — keep the default ``repl=1`` when judging it."""
        from arrow_matrix_tpu.obs.memview import tree_device_bytes

        n_dev = self.mesh.shape[self.axis]
        ops_bytes = sum(o.device_nbytes() for o in self.ops)
        ops_bytes += tree_device_bytes(self.fwd, self.bwd)
        base = (ops_bytes // n_dev
                + 2 * self.ops[0].rows_out * k * itemsize)
        return base * max(int(repl), 1)

    def shard_report(self) -> dict:
        """Per-device load report summed over the decomposition levels
        (every level's shard runs on the same device, so a device's
        compute is the sum of its per-level tiers)."""
        from arrow_matrix_tpu.obs.imbalance import summarize_units

        n_dev = self.mesh.shape[self.axis]
        nnz = np.zeros(n_dev, dtype=np.int64)
        slots = np.zeros(n_dev, dtype=np.int64)
        rows = np.zeros(n_dev, dtype=np.int64)
        for o in self.ops:
            for stack in (o.body, o.head):
                s_nnz, s_slots = stack.shard_stats()
                nnz += s_nnz
                slots += s_slots
            rows += o.rows_out
        return summarize_units(rows, nnz, slots, units="device")

    def carried_mask(self) -> jax.Array:
        """(1, total_out_0) f32 validity mask of the carried ordering:
        1 where a position holds a real original row, 0 at tier
        padding.  Whole-state reductions (norms, dot products — e.g.
        power iteration) must mask pads: after a step they hold routed
        filler, not zeros."""
        m = _live(self._orig_of_pos0, self.n).astype(np.float32)[None, :]
        return put_global(
            m, NamedSharding(self.mesh, P(None, self.axis)))
