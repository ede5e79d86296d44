"""PETSc-style 1-D row-partitioned distributed SpMM baseline.

TPU-native counterpart of the reference's general-sparsity baseline
(reference arrow/matrix_slice.py + arrow/baseline/spmm_petsc.py).  The
reference gives each MPI rank a row slice ``A_i``, splits it into a
*local* part (columns inside the rank's own row range) and a *nonlocal*
part (columns gathered from other ranks), and precomputes exact
row-exchange tables from the sparsity pattern at init:

  * receive tables — which X rows this rank needs from which owner, from
    the nonzero off-slice columns (matrix_slice.py:184-227);
  * send tables — the transpose, exchanged via Alltoall counts +
    Alltoallv indices (matrix_slice.py:233-273);

so the per-iteration path is pure buffer exchange: Isend/Irecv exactly
the needed rows — one message per rank pair — overlapped with the local
CSRMM (spmm_petsc.py:105-144,179-221).

Here the tables are built *globally* at construction (the sparsity
pattern is host-resident anyway) and become static index arrays driving
one `lax.all_to_all` under `shard_map`:

  MPI primitive (reference)               this module
  --------------------------------------  ------------------------------
  per-pair Isend/Irecv of exact rows       one `all_to_all` over padded
    (spmm_petsc.py:105-144)                 fixed-size slots
  gathered nonlocal column renumbering     static nonlocal ELL column
    (matrix_slice.py:117-139)               indices into the recv buffer
  collective table verification            consistency asserted at
    (matrix_slice.py:157-182)               construction (tables are
                                            derived from one global view)

Ragged slices (the reference supports unequal and even zero-row slices,
tests/test_spmmPETSc.py:44-71) are padded to one static slice height;
padding rows are zero and never referenced by the exchange tables.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from arrow_matrix_tpu.parallel.mesh import (
    build_global,
    build_global_parts,
    fetch_replicated,
    put_global,
    
)
from scipy import sparse

from arrow_matrix_tpu.ops.ell import align_up, ell_pack


def _owned_slice_ids(mesh: Mesh, axis: str) -> set:
    """Slice ids whose mesh-axis device group includes a device of THIS
    process (single-process: all of them)."""
    ax = list(mesh.axis_names).index(axis)
    groups = np.moveaxis(mesh.devices, ax, 0).reshape(mesh.shape[axis], -1)
    pid = jax.process_index()
    return {d for d in range(groups.shape[0])
            if any(dev.process_index == pid for dev in groups[d])}


def _primary_slice_ids(mesh: Mesh, axis: str) -> set:
    """Slice ids whose FIRST device belongs to this process — exactly
    one primary per slice.  Metadata exchanged by summation
    (_exchange_sum) must be contributed only by primaries: on a mesh
    with extra axes a slice's device group can span processes, and a
    per-owner contribution would multiply the sums."""
    ax = list(mesh.axis_names).index(axis)
    groups = np.moveaxis(mesh.devices, ax, 0).reshape(mesh.shape[axis], -1)
    pid = jax.process_index()
    return {d for d in range(groups.shape[0])
            if groups[d][0].process_index == pid}


def _load_slice(src, dtype) -> sparse.csr_matrix:
    """One slice source -> canonical CSR: a scipy matrix, a ``.npz``
    path (the reference's ``{name}.part.{P}.slice.{r}.npz`` files,
    spmm_petsc.py:82-102), or a zero-arg callable returning either."""
    if callable(src):
        src = src()
    if isinstance(src, str):
        src = sparse.load_npz(src)
    if not sparse.issparse(src):
        raise TypeError(
            f"slice source must be a scipy matrix, path, or callable, "
            f"got {type(src).__name__}")
    m = src.tocsr().astype(dtype)
    m.sum_duplicates()
    return m


def _exchange_sum(arr: np.ndarray) -> np.ndarray:
    """Combine per-process contributions (zeros at non-owned entries)
    into the global array — the host-side counterpart of the
    reference's Alltoall of counts (matrix_slice.py:233-248).
    Identity in single-process runs."""
    if jax.process_count() == 1:
        return arr
    from jax.experimental import multihost_utils

    stacked = np.asarray(multihost_utils.process_allgather(arr))
    return stacked.sum(axis=0)


def _exchange_ragged(mine: dict, lens: np.ndarray, n_dev: int
                     ) -> List[np.ndarray]:
    """Owned ragged int64 arrays -> every slice's array on every
    process (the reference's Alltoallv of indices,
    matrix_slice.py:248-273), padded to the global max for the
    fixed-shape allgather."""
    lens = np.asarray(lens, dtype=np.int64)
    if jax.process_count() == 1:
        return [np.asarray(mine.get(d, np.zeros(0, np.int64)))
                for d in range(n_dev)]
    maxlen = int(lens.max()) if lens.size else 0
    mat = np.zeros((n_dev, maxlen), dtype=np.int64)
    for d, arr in mine.items():
        mat[d, :arr.size] = arr
    mat = _exchange_sum(mat)
    return [mat[d, :lens[d]] for d in range(n_dev)]


def equal_slices(n: int, n_dev: int) -> List[Tuple[int, int]]:
    """Contiguous near-equal row ranges (the reference's default
    partition when slices are pre-cut, spmm_petsc.py:82-102)."""
    bounds = np.linspace(0, n, n_dev + 1).astype(np.int64)
    return [(int(bounds[i]), int(bounds[i + 1])) for i in range(n_dev)]


class MatrixSlice1D:
    """1-D row-partitioned SpMM with exact-row exchange on a mesh axis.

    Reference ``MatrixSlice.initialize`` analog (matrix_slice.py:106-154):
    construction splits each slice into local/nonlocal ELL blocks, builds
    the send tables and the nonlocal column renumbering, and jits the
    exchange + two-SpMM step.  ``spmm(x)`` preserves the blocked feature
    layout, so iterating runs the reference benchmark loop
    (spmm_petsc.py:471-492).
    """

    def __init__(self, a: sparse.spmatrix, mesh: Mesh, axis: str = "slices",
                 slices: Optional[Sequence[Tuple[int, int]]] = None,
                 dtype=np.float32, chunk=None,
                 memory_fraction: float = 0.5):
        """``chunk``: slot-chunk bound for the two ELL gathers — an
        explicit int, None (no chunking), or "auto": sized at trace
        time from ``memory_fraction`` of the device's currently-free
        memory net of this layout's own resident blocks (the
        reference's OOM-model GPU tiling, spmm_petsc.py:323-395), with
        a shared-pool division on host-CPU meshes where all shards
        draw from one physical RAM."""
        self.mesh = mesh
        self.axis = axis
        n_dev = mesh.shape[axis]
        self.n_dev = n_dev

        # -- slice sources.  A global view (scipy matrix) is cut into
        # per-device slabs here; a SEQUENCE is per-slice sources —
        # scipy matrices, ``.npz`` paths, or callables returning either
        # — and each process loads ONLY the slices of devices it owns
        # (the reference's per-rank slice files,
        # spmm_petsc.py:421-440).  Cross-slice metadata (row counts,
        # needed-row patterns, slot needs) is exchanged host-side (the
        # reference's Alltoall of counts + Alltoallv of indices,
        # matrix_slice.py:233-273).
        mine = _owned_slice_ids(mesh, axis)
        primary = _primary_slice_ids(mesh, axis)
        if sparse.issparse(a):
            a = a.tocsr().astype(dtype)
            a.sum_duplicates()
            n, nc = a.shape
            if n != nc:
                raise ValueError("iterated SpMM needs a square matrix")
            self.slices = (list(slices) if slices is not None
                           else equal_slices(n, n_dev))
            if len(self.slices) != n_dev:
                raise ValueError(
                    f"{len(self.slices)} slices for {n_dev} devices")
            slabs = {d: a[lo:hi].tocsr()
                     for d, (lo, hi) in enumerate(self.slices)}
            rows_per = np.asarray([hi - lo for lo, hi in self.slices],
                                  dtype=np.int64)
        else:
            sources = list(a)
            if len(sources) != n_dev:
                raise ValueError(
                    f"{len(sources)} slice sources for {n_dev} devices")
            slabs = {d: _load_slice(sources[d], dtype) for d in mine}
            widths = {m.shape[1] for m in slabs.values()}
            if len(widths) > 1:
                raise ValueError(f"slice widths disagree: {widths}")
            rows_mine = np.zeros(n_dev, dtype=np.int64)
            for d, m in slabs.items():
                if d in primary:   # one contributor per slice
                    rows_mine[d] = m.shape[0]
            rows_per = _exchange_sum(rows_mine)
            n = int(rows_per.sum())
            if slabs and next(iter(slabs.values())).shape[1] != n:
                raise ValueError(
                    f"slice width {next(iter(slabs.values())).shape[1]} "
                    f"!= total rows {n} (iterated SpMM needs square)")
            bounds = np.concatenate([[0], np.cumsum(rows_per)])
            self.slices = [(int(bounds[d]), int(bounds[d + 1]))
                           for d in range(n_dev)]
            if slices is not None and list(slices) != self.slices:
                raise ValueError("explicit slices disagree with the "
                                 "per-source row counts")
        self.n = n
        starts = np.asarray([s for s, _ in self.slices], dtype=np.int64)
        stops = np.asarray([t for _, t in self.slices], dtype=np.int64)
        if starts[0] != 0 or stops[-1] != n or np.any(starts[1:] != stops[:-1]):
            raise ValueError("slices must tile [0, n) contiguously")
        self.l_rows = int((stops - starts).max()) if n_dev else 0
        self.l_rows = max(self.l_rows, 1)

        # -- receive patterns: the off-slice columns each OWNED slice
        # needs, already sorted — and therefore already grouped by
        # owner (owners are monotone over contiguous slices): the
        # concatenated per-source order of the reference's gathered
        # nonlocal columns (matrix_slice.py:184-227).  Per-slab ELL
        # slot needs are collected in the same pass.
        off_mine: dict = {}
        cnt_mine = np.zeros((n_dev, n_dev), dtype=np.int64)  # [src, dst]
        need_mine = np.zeros((2, n_dev), dtype=np.int64)     # local/nonlocal
        for d, slab in slabs.items():
            if d not in primary:   # metadata: one contributor per slice
                continue
            lo, hi = self.slices[d]
            is_local = (slab.indices >= lo) & (slab.indices < hi)
            off_mine[d] = np.unique(slab.indices[~is_local]).astype(np.int64)
            owners = np.searchsorted(stops, off_mine[d], side="right")
            cnt_mine[:, d] = np.bincount(owners, minlength=n_dev)
            if slab.nnz:
                row_of = np.repeat(np.arange(slab.shape[0], dtype=np.int64),
                                   np.diff(slab.indptr))
                for part, mask in ((0, is_local), (1, ~is_local)):
                    if mask.any():
                        need_mine[part, d] = int(np.bincount(
                            row_of[mask], minlength=slab.shape[0]).max())

        if jax.process_count() == 1:
            # Single process: the tables are already complete.  The
            # guard must be on the PROCESS COUNT, not on "primary for
            # every slice" — a process that happens to be primary
            # everywhere (e.g. a ('repl', 'slices') mesh whose first
            # devices all live on process 0) skipping the exchange
            # would strand its peers at the collective.
            counts, needs = cnt_mine, need_mine
            off_all = [off_mine.get(d, np.zeros(0, np.int64))
                       for d in range(n_dev)]
        else:
            counts = _exchange_sum(cnt_mine)
            needs = _exchange_sum(need_mine)
            off_all = _exchange_ragged(off_mine, counts.sum(axis=0), n_dev)
        # Fixed per-pair slot count: the Alltoallv's ragged counts
        # (matrix_slice.py:248-252) become one padded slot size.
        self.slot = int(counts.max()) if counts.size else 0
        slot = self.slot
        # Paper cost model (reference Alltoallv payload): rows actually
        # needed across devices, before the fixed-slot padding the
        # all_to_all ships — obs/comm compares compiled HLO bytes
        # against ideal_comm_bytes built on this.
        self._ideal_route_rows = int(counts.sum()) if counts.size else 0

        # -- send tables: send_idx[s, d] = local row indices device s
        # ships to device d, read off the exchanged patterns.
        cnt_cum = np.concatenate(
            [np.zeros((1, n_dev), np.int64), np.cumsum(counts, axis=0)])

        def _build_send(idx):
            (s_sl,) = idx[:1]
            out = np.zeros((s_sl.stop - s_sl.start, 1, n_dev, slot),
                           dtype=np.int32)
            for row_i, s in enumerate(range(s_sl.start, s_sl.stop)):
                for d in range(n_dev):
                    rows = off_all[d][cnt_cum[s, d]:cnt_cum[s + 1, d]]
                    out[row_i, 0, d, :rows.size] = rows - starts[s]
            return out

        # -- per-device local/nonlocal ELL blocks with shared slot
        # counts, built ONLY for this process's shards (build_global).
        m_l = align_up(int(needs[0].max()), 8) if needs[0].max() else 0
        m_nl = align_up(int(needs[1].max()), 8) if needs[1].max() else 0

        def _split(d: int, part: int):
            slab = slabs[d]   # owned by construction of the sharding
            lo, hi = self.slices[d]
            in_range = (slab.indices >= lo) & (slab.indices < hi)
            m = slab.copy()
            m.data = np.where(in_range if part == 0 else ~in_range,
                              slab.data, 0)
            m.eliminate_zeros()
            if part == 0:
                # Local column index == row index within the padded slice.
                return sparse.csr_matrix(
                    (m.data, m.indices - lo, m.indptr),
                    shape=(hi - lo, self.l_rows))
            # Renumber nonlocal columns into the (n_dev * slot) receive
            # buffer: global row g owned by s at position p within the
            # rows-from-s list lands at s * slot + p
            # (matrix_slice.py:117-139 gathered-column renumbering);
            # off_all[d] is sorted, so the remap is one searchsorted.
            needed = off_all[d]
            owners = np.searchsorted(stops, needed, side="right")
            within = (np.arange(needed.size)
                      - cnt_cum[owners, d]) if needed.size else needed
            buf_pos = owners * slot + within
            new_cols = (buf_pos[np.searchsorted(needed, m.indices)]
                        if m.nnz else np.zeros(0, dtype=np.int64))
            return sparse.csr_matrix(
                (m.data, new_cols.astype(np.int64), m.indptr),
                shape=(hi - lo, max(n_dev * slot, 1)))

        def _build_blocks(idx, part: int):
            """One shard's (cols, data) pair for the local (part 0) or
            nonlocal (part 1) stack — packed once per shard, both
            parts together."""
            (d_sl,) = idx[:1]
            m_slots = m_l if part == 0 else m_nl
            cols = np.zeros((d_sl.stop - d_sl.start, self.l_rows, m_slots),
                            dtype=np.int32)
            data = np.zeros_like(cols, dtype=dtype)
            for row_i, d in enumerate(range(d_sl.start, d_sl.stop)):
                c, dd = ell_pack(_split(d, part), max_nnz=m_slots,
                                 dtype=dtype)
                cols[row_i, :c.shape[0]] = c
                data[row_i, :dd.shape[0]] = dd
            return cols, data

        shard = NamedSharding(mesh, P(axis))
        l_shape = (n_dev, self.l_rows, m_l)
        nl_shape = (n_dev, self.l_rows, m_nl)
        send_shape = (n_dev, 1, n_dev, slot)
        itemsize = np.dtype(dtype).itemsize
        if chunk == "auto":
            if not 0 < memory_fraction <= 1:
                raise ValueError(
                    f"memory_fraction must be in (0, 1], got "
                    f"{memory_fraction}")
            from arrow_matrix_tpu.utils.platform import device_memory_budget

            block_bytes = int(
                np.prod(l_shape) * (4 + itemsize)
                + np.prod(nl_shape) * (4 + itemsize)
                + np.prod(send_shape) * 4)
            dev = mesh.devices.flat[0]
            budget = device_memory_budget(dev, fraction=memory_fraction)
            floor = 1 << 26
            if dev.platform == "cpu":
                # Virtual devices share one physical pool: net out ALL
                # resident blocks and split across concurrent shards.
                per_dev = max(budget - block_bytes, floor) / max(n_dev, 1)
            else:
                per_dev = max(budget - block_bytes / max(n_dev, 1), floor)
            chunk = ("auto", int(per_dev))

        self.l_cols, self.l_data = build_global_parts(
            l_shape, shard, lambda i: _build_blocks(i, 0),
            (np.int32, dtype))
        self.nl_cols, self.nl_data = build_global_parts(
            nl_shape, shard, lambda i: _build_blocks(i, 1),
            (np.int32, dtype))
        self.send_idx = build_global(send_shape, shard, _build_send,
                                     np.int32)

        l_rows = self.l_rows

        def local_step(l_cols, l_data, nl_cols, nl_data, send_idx, x):
            # All operands carry this device's leading slice of size 1.
            x_loc = x[0]                       # (l_rows, k)
            k = x_loc.shape[-1]
            from arrow_matrix_tpu.ops.ell import auto_chunk, ell_spmm

            if isinstance(chunk, tuple):       # ("auto", budget_bytes)
                budget = chunk[1]
                c_l = auto_chunk(l_rows, k, l_cols.shape[-1], budget)
                c_nl = auto_chunk(l_rows, k, nl_cols.shape[-1], budget)
            else:
                c_l = c_nl = chunk

            # Local SpMM first: in the reference it overlaps with the
            # in-flight row exchange (spmm_petsc.py:193-199); under XLA
            # the scheduler overlaps the independent all_to_all for us.
            with jax.named_scope("local_spmm"):
                y = ell_spmm(l_cols[0], l_data[0], x_loc,
                             chunk=c_l).astype(jnp.float32)

            if slot > 0:
                # Ship exactly the requested rows to every peer: one
                # fused all_to_all replaces the per-pair Isend/Irecv
                # (spmm_petsc.py:105-144).
                with jax.named_scope("route_rows"):
                    send = jnp.take(x_loc, send_idx[0, 0], axis=0)  # (n_dev, slot, k)
                    recv = lax.all_to_all(send, axis, split_axis=0,
                                          concat_axis=0, tiled=True)
                    x_nonlocal = recv.reshape(slot * send.shape[0], k)
                with jax.named_scope("nonlocal_spmm"):
                    y = y + ell_spmm(nl_cols[0], nl_data[0], x_nonlocal,
                                     chunk=c_nl).astype(jnp.float32)
            return y[None].astype(x.dtype)

        self._step = jax.jit(shard_map(
            local_step, mesh=mesh,
            in_specs=(P(axis), P(axis), P(axis), P(axis), P(axis), P(axis)),
            out_specs=P(axis),
            check_vma=False,
        ))

    # -- feature placement -------------------------------------------------

    def set_features(self, x: np.ndarray) -> jax.Array:
        """Host (n, k) features -> blocked (n_dev, l_rows, k) sharded
        array; ragged slices pad with zero rows at each slice tail."""
        n, k = x.shape
        if n != self.n:
            raise ValueError(f"expected {self.n} rows, got {n}")
        blocked = np.zeros((self.n_dev, self.l_rows, k), dtype=x.dtype)
        for d, (lo, hi) in enumerate(self.slices):
            blocked[d, :hi - lo] = x[lo:hi]
        return put_global(blocked,
                          NamedSharding(self.mesh, P(self.axis)))

    def spmm(self, x: jax.Array) -> jax.Array:
        """One distributed SpMM preserving the blocked layout."""
        return self._step(self.l_cols, self.l_data, self.nl_cols,
                          self.nl_data, self.send_idx, x)

    def ideal_comm_bytes(self, k: int, itemsize: int = 4) -> int:
        """Paper cost model for one step at feature width ``k``: only
        the rows peers actually request move (the reference Alltoallv
        payload) — the all_to_all's fixed-slot padding is overhead the
        measured/ideal ratio exposes."""
        return self._ideal_route_rows * k * itemsize

    def collective_contract(self, k: int, itemsize: int = 4):
        """Static communication promise for graft-prove: the petsc-1D
        step's only exchange is the fixed-slot nonlocal-row all_to_all
        (no replication, no overlap schedule, no donated entry).  HLO
        counts one device's fixed-slot tuple once; the ideal counts
        every device's requested rows — hence a ratio well under 1 at
        small scale."""
        from arrow_matrix_tpu.analysis.contracts import CollectiveContract

        return CollectiveContract(
            algorithm="spmm_1d",
            step_bytes=self.ideal_comm_bytes(k, itemsize),
            reduce_bytes=0,
            repl=1,
            overlap_slabs=1,
            dtype="f32",
            lowered_kinds=("all-to-all",),
            compiled_kinds=("all-to-all",),
            ratio_band=(0.05, 2.0),
            notes="fixed-slot a2a padding vs requested-row ideal "
                  "(the reference Alltoallv payload)")

    def predicted_hbm_bytes(self, k: int, itemsize: int = 4) -> int:
        """Static per-shard HBM model for one step at feature width
        ``k``: this device's slice of the ELL stacks and exchange
        tables (all carry a leading device axis) plus the blocked
        feature input and output (l_rows each).  obs/memview judges
        the compiled executable against this."""
        from arrow_matrix_tpu.obs.memview import tree_device_bytes

        ops_bytes = tree_device_bytes(
            (self.l_cols, self.l_data, self.nl_cols, self.nl_data,
             self.send_idx))
        return ops_bytes // self.n_dev + 2 * self.l_rows * k * itemsize

    def shard_report(self) -> dict:
        """Per-device load report from the packed slice metadata
        (obs/imbalance.py schema): rows actually owned per slice, local
        + nonlocal nonzeros vs padded ELL slots."""
        from arrow_matrix_tpu.obs.imbalance import summarize_units
        from arrow_matrix_tpu.ops.ell import ell_slot_stats

        l_nnz, l_slots = ell_slot_stats(self.l_cols, self.l_data)
        nl_nnz, nl_slots = ell_slot_stats(self.nl_cols, self.nl_data)
        rows = [hi - lo for lo, hi in self.slices]
        return summarize_units(rows, l_nnz + nl_nnz, l_slots + nl_slots,
                               units="device")

    def gather_result(self, y: jax.Array) -> np.ndarray:
        """Blocked (n_dev, l_rows, k) device result -> host (n, k)."""
        arr = fetch_replicated(y)
        out = np.empty((self.n, arr.shape[-1]), dtype=arr.dtype)
        for d, (lo, hi) in enumerate(self.slices):
            out[lo:hi] = arr[d, :hi - lo]
        return out
