"""Device-resident arrow matrix blocks and the single-device SpMM step.

An arrow matrix of ``nb`` block-rows of width ``w`` has nonzero blocks
only at (0, j), (i, 0), (i, i) and — in banded mode — (i, i+-1)
(reference arrow/common/graphio.py:382,438).  On TPU the natural layout
is *stacked ELL arrays with a leading block axis*:

    head:  (nb, w, m_h)   block j holds A_{0j}  (the head row chunk)
    diag:  (nb, w, m_d)   block i holds A_{ii}  (empty at i = 0)
    col:   (nb, w, m_c)   block i holds A_{i0}  (empty at i = 0)
    lo/hi: (nb, w, m_b)   banded only: A_{i,i-1} / A_{i,i+1}

The leading axis is the unit of sharding: `shard_map` over a mesh axis
gives each device a contiguous slice of block-rows, and the identical
per-block compute below runs unchanged inside or outside the mesh.  The
reference's two MPI layouts collapse onto this one representation: the
"slim" layout (one rank per block-row, reference arrow/arrow_slim_mpi.py)
is the sharding itself, and the "wide" layout's separate row-arm ranks
(reference arrow/arrow_mpi.py:31-47) exist only to parallelize the
head-row reduction, which `psum` over ICI already does.

Semantics of one SpMM ``C = B @ X`` (X blocked like the rows):
    C_0 = sum_j A_0j X_j                    (head row; psum / sum)
    C_i = A_ii X_i + A_i0 X_0 [+ A_i,i-1 X_{i-1} + A_i,i+1 X_{i+1}]
(reference arrow/arrow_slim_mpi.py:104-147, arrow/arrow_mpi.py:177-299.)
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct
from scipy import sparse

from arrow_matrix_tpu.io.graphio import (
    CsrLike,
    load_block,
    num_nonzeros,
    num_rows,
    number_of_blocks,
)
from arrow_matrix_tpu.ops.ell import (
    dense_pack_stack,
    dense_spmm_batched,
    ell_pack_stack,
    ell_spmm,
    ell_spmm_batched,
)


@struct.dataclass
class ArrowBlocks:
    """Pytree of stacked ELL arrays for one arrow matrix (one level).

    Binary (implicit-ones) matrices — graph adjacency, the dominant
    workload — drop every ``*_data`` value stack (None) and carry
    per-row degree stacks ``*_deg`` (nb, w) instead: the slot-validity
    mask is generated in registers by the kernels (ops/ell.py), halving
    the streamed slot bytes.  Flat-COO heads need neither values nor
    degrees in binary mode (padding entries scatter into the dummy
    row).  Applies to ``fmt="ell"`` only; dense blocks always carry
    values.
    """

    head_cols: jax.Array = None
    head_data: Optional[jax.Array] = None
    diag_cols: jax.Array = None
    diag_data: Optional[jax.Array] = None
    col_cols: jax.Array = None
    col_data: Optional[jax.Array] = None
    lo_cols: Optional[jax.Array] = None
    lo_data: Optional[jax.Array] = None
    hi_cols: Optional[jax.Array] = None
    hi_data: Optional[jax.Array] = None
    # Flat-COO head (head_flat=True): head_rows/head_cols/head_data are
    # (nb, B) per-block entry lists and the head SpMM is a scatter-add.
    # The arrow head's rows are the pruned high-degree vertices, so ELL
    # row padding there can blow up by orders of magnitude (measured
    # 150x on a 400k-row Barabasi graph); flat packing is O(nnz).
    head_rows: Optional[jax.Array] = None
    # Degree stacks for binary mode ((nb, w) int32; gell head: (w,)).
    head_deg: Optional[jax.Array] = None
    diag_deg: Optional[jax.Array] = None
    col_deg: Optional[jax.Array] = None
    lo_deg: Optional[jax.Array] = None
    hi_deg: Optional[jax.Array] = None

    width: int = struct.field(pytree_node=False, default=0)
    n_blocks: int = struct.field(pytree_node=False, default=0)
    banded: bool = struct.field(pytree_node=False, default=False)
    # Block storage format: "ell" (gather-based, for widths too large to
    # densify) or "dense" ((nb, w, w) blocks -> batched MXU matmuls; the
    # *_cols arrays are empty).  An arrow matrix has ~3 structural blocks
    # per block-row, so dense costs 3·n·w memory at n rows / width w.
    fmt: str = struct.field(pytree_node=False, default="ell")
    head_flat: bool = struct.field(pytree_node=False, default=False)
    # Global-row ELL head (head_gell=True): head_cols/head_data are
    # (w, m) over GLOBAL column indices — the head has only w rows, so
    # one ELL over the whole row space is compact even when per-block
    # ELL would degenerate to dense, and the compute is a chunked
    # gather+reduce instead of the flat head's scatter-add (TPU
    # scatters serialize; gathers stream).  Single-chip layout: the
    # gather reads the whole feature array, so it does not shard.
    head_gell: bool = struct.field(pytree_node=False, default=False)

    @property
    def binary(self) -> bool:
        return self.diag_data is None

    @property
    def n_rows(self) -> int:
        return self.width * self.n_blocks

    def device_nbytes(self) -> int:
        total = 0
        for leaf in jax.tree_util.tree_leaves(self):
            total += leaf.size * leaf.dtype.itemsize
        return total


def scipy_safe_dtype(dtype):
    """scipy.sparse cannot hold narrow dtypes like bf16; blocks pass
    through scipy at f32 and are cast to the storage dtype only at the
    numpy packing step (ell/dense/flat packers)."""
    try:
        sparse.csr_matrix((0, 0), dtype=dtype)
        return dtype
    except ValueError:
        return np.float32


def arrow_blocks_from_csr(matrix: CsrLike, width: int,
                          n_blocks: Optional[int] = None,
                          banded: bool = False,
                          pad_blocks_to: Optional[int] = None,
                          dtype=np.float32,
                          check: bool = True,
                          fmt: str = "ell",
                          head_fmt: str = "auto",
                          binary="auto") -> ArrowBlocks:
    """Tile an arrow-shaped CSR (or memmapped triplet) into ArrowBlocks.

    Trailing all-zero rows beyond ``n_blocks * width`` are truncated
    (reference arrow_dec_mpi.py:612-627); ``pad_blocks_to`` appends empty
    block-rows so every level of a decomposition can share one static
    block count (needed for a uniform mesh sharding).

    With ``check`` (default) the tiling verifies that the arrow-pattern
    blocks capture *every* nonzero of the matrix: a matrix wider than
    ``width`` (e.g. a decomposition's last level whose achieved width
    grew) would otherwise be silently mangled — the reference drops such
    nonzeros without any diagnostic.  Requires a canonical (duplicate-
    free) input, which this framework's loaders guarantee.

    ``head_fmt`` governs the head stack under ``fmt="ell"``: "flat"
    packs the head blocks as per-block flat-COO entry lists (O(nnz) —
    immune to the head's skewed row degrees), "ell" keeps the uniform
    ELL layout, "auto" picks flat whenever it is at least 4x smaller.
    """
    nb = n_blocks if n_blocks is not None else number_of_blocks(matrix, width)
    nb_padded = max(pad_blocks_to or nb, nb)
    captured = 0
    host_dtype = scipy_safe_dtype(dtype)
    is_binary = resolve_blocks_binary(matrix, fmt, binary)
    from arrow_matrix_tpu.ops.ell import block_index_dtype

    idt = block_index_dtype(width)

    def blk(i, j):
        nonlocal captured
        b = load_block(matrix, i * width, (i + 1) * width,
                       j * width, (j + 1) * width, width, dtype=host_dtype)
        captured += b.nnz
        return b

    if fmt not in ("ell", "dense"):
        raise ValueError(f"unknown block format {fmt!r}")

    def pack(mats):
        """(cols, data, deg) — data None / deg present in binary mode."""
        if fmt == "dense":
            no_cols = np.zeros((len(mats), 0, 0), dtype=np.int32)
            return (no_cols, dense_pack_stack(mats, dtype=dtype, rows=width),
                    None)
        if is_binary:
            from arrow_matrix_tpu.ops.ell import ell_pack_stack_binary

            cols, deg = ell_pack_stack_binary(mats, rows=width,
                                              index_dtype=idt)
            return cols, None, deg
        cols, data = ell_pack_stack(mats, dtype=dtype, rows=width,
                                    index_dtype=idt)
        return cols, data, None

    head_rows = None
    head_deg = None
    head_flat = False
    head_gell = fmt == "ell" and head_fmt == "gell"
    if head_gell:
        head_cols, head_data, head_nnz, head_deg = _gell_head_pack(
            matrix, width, dtype=dtype, binary=is_binary)
        captured += head_nnz
    else:
        head = [blk(0, j) if j < nb else None for j in range(nb_padded)]
        head_flat = fmt == "ell" and _choose_flat_head(head, width, dtype,
                                                       head_fmt)
        if head_flat:
            from arrow_matrix_tpu.ops.ell import flat_pack_stack

            head_rows, head_cols, head_data = flat_pack_stack(
                head, dtype=dtype, rows=width, index_dtype=idt)
            if is_binary:
                head_data = None   # dummy-row scatter needs no values
        else:
            head_cols, head_data, head_deg = pack(head)
    diag = [None] + [blk(i, i) if i < nb else None for i in range(1, nb_padded)]
    col = [None] + [blk(i, 0) if i < nb else None for i in range(1, nb_padded)]
    diag_cols, diag_data, diag_deg = pack(diag)
    col_cols, col_data, col_deg = pack(col)

    def dev(a):
        return None if a is None else jnp.asarray(a)

    kw = {}
    if banded:
        lo = [None, None] + [blk(i, i - 1) if i < nb else None
                             for i in range(2, nb_padded)]
        hi = [None] + [blk(i, i + 1) if i + 1 < nb else None
                       for i in range(1, nb_padded)]
        lo_cols, lo_data, lo_deg = pack(lo)
        hi_cols, hi_data, hi_deg = pack(hi)
        kw = dict(lo_cols=jnp.asarray(lo_cols), lo_data=dev(lo_data),
                  hi_cols=jnp.asarray(hi_cols), hi_data=dev(hi_data),
                  lo_deg=dev(lo_deg), hi_deg=dev(hi_deg))

    if check:
        total = num_nonzeros(matrix)
        if captured != total:
            raise ValueError(
                f"arrow tiling captured {captured} of {total} nonzeros: the "
                f"matrix has entries outside the {'banded' if banded else 'block-diagonal'} "
                f"arrow pattern at width {width} / {nb} blocks (did the last "
                f"level's achieved width exceed the requested width?)")

    return ArrowBlocks(
        head_cols=jnp.asarray(head_cols), head_data=dev(head_data),
        diag_cols=jnp.asarray(diag_cols), diag_data=dev(diag_data),
        col_cols=jnp.asarray(col_cols), col_data=dev(col_data),
        head_rows=(jnp.asarray(head_rows) if head_rows is not None
                   else None),
        head_deg=dev(head_deg), diag_deg=dev(diag_deg), col_deg=dev(col_deg),
        width=width, n_blocks=nb_padded, banded=banded, fmt=fmt,
        head_flat=head_flat, head_gell=head_gell, **kw)


def resolve_blocks_binary(matrix: CsrLike, fmt: str, binary) -> bool:
    """Level-wide binary decision for the stacked formats: implicit-ones
    triplets are binary, "auto" detects all-ones CSR values; dense
    blocks always carry values (the MXU multiplies anyway)."""
    if fmt == "dense":
        return False
    from arrow_matrix_tpu.ops.sell import resolve_binary

    if isinstance(matrix, sparse.csr_matrix):
        return resolve_binary(binary, matrix.data, nnz=matrix.nnz)
    data, _, indptr = matrix
    return resolve_binary(binary, data, nnz=int(np.asarray(indptr[-1])))


def _gell_head_pack(matrix: CsrLike, width: int, dtype=np.float32,
                    binary: bool = False
                    ) -> tuple[np.ndarray, Optional[np.ndarray], int,
                               Optional[np.ndarray]]:
    """Head rows [0, width) packed as ONE (width, m) ELL over *global*
    column indices (see ArrowBlocks.head_gell).  Returns
    (cols, data, nnz, deg); m is the max head-row degree, slot-aligned;
    binary mode returns data=None with deg (width,) int32."""
    from arrow_matrix_tpu.ops.ell import SLOT_ALIGN, align_up, ell_pack

    n = num_rows(matrix)
    if isinstance(matrix, sparse.csr_matrix):
        data, indices, indptr = matrix.data, matrix.indices, matrix.indptr
    else:
        data, indices, indptr = matrix
    w_eff = min(width, n)
    hi = int(indptr[w_eff])
    sub_indptr = np.asarray(indptr[:w_eff + 1], dtype=np.int64)
    if w_eff < width:  # empty padding rows
        sub_indptr = np.pad(sub_indptr, (0, width - w_eff), mode="edge")
    sub_data = (np.ones(hi, dtype=np.float32) if data is None
                else np.asarray(data[:hi]))
    sub = sparse.csr_matrix((sub_data, np.asarray(indices[:hi]), sub_indptr),
                            shape=(width, n))
    counts = np.diff(sub.indptr)
    need = int(counts.max()) if counts.size and counts.max() > 0 else 0
    m = align_up(need, SLOT_ALIGN) if need else 0
    cols, packed = ell_pack(sub, max_nnz=m, dtype=dtype)
    if binary:
        return cols, None, hi, counts.astype(np.int32)
    return cols, packed, hi, None


def choose_flat_head_from_stats(nb: int, width: int, max_row_nnz: int,
                                max_block_nnz: int, dtype,
                                head_fmt: str) -> bool:
    """One flat-vs-ELL head decision shared by the eager and streamed
    builders (they MUST agree: streamed promises bit-identical output).
    "auto" picks flat when the flat footprint is at least 4x smaller."""
    if head_fmt == "flat":
        return True
    if head_fmt == "ell":
        return False
    if head_fmt != "auto":
        raise ValueError(f"unknown head format {head_fmt!r}")
    from arrow_matrix_tpu.ops.ell import SLOT_ALIGN, align_up

    itemsize = np.dtype(dtype).itemsize
    ell = nb * width * align_up(max_row_nnz, SLOT_ALIGN) * (4 + itemsize)
    flat = nb * align_up(max_block_nnz, SLOT_ALIGN) * (8 + itemsize)
    return flat * 4 <= ell


def head_stats(matrix: CsrLike, width: int, nb: int) -> tuple[int, int]:
    """(max row nnz, max block nnz) over the head-row blocks A_0j —
    the inputs of the flat-vs-ELL head decision, computed by loading
    ONLY the head blocks (so callers can pre-agree a head format
    across levels without building, then build once)."""
    max_row = max_nnz = 0
    for j in range(nb):
        b = load_block(matrix, 0, width, j * width, (j + 1) * width, width)
        counts = np.diff(b.indptr)
        if counts.size:
            max_row = max(max_row, int(counts.max()))
        max_nnz = max(max_nnz, int(b.nnz))
    return max_row, max_nnz


def _choose_flat_head(head, width: int, dtype, head_fmt: str) -> bool:
    max_row = 0
    max_nnz = 0
    for m in head:
        if m is None or m.nnz == 0:
            continue
        counts = np.diff(m.tocsr().indptr)
        if counts.size:
            max_row = max(max_row, int(counts.max()))
        max_nnz = max(max_nnz, int(m.nnz))
    return choose_flat_head_from_stats(len(head), width, max_row, max_nnz,
                                       dtype, head_fmt)


def _stack_coords(nb: int, nb_padded: int, banded: bool
                  ) -> dict[str, list[Optional[tuple[int, int]]]]:
    """Per-stack block coordinates, None for structurally-empty slots
    (mirrors the list construction in ``arrow_blocks_from_csr``)."""
    coords: dict[str, list[Optional[tuple[int, int]]]] = {
        "head": [(0, j) if j < nb else None for j in range(nb_padded)],
        "diag": [None] + [(i, i) if i < nb else None
                          for i in range(1, nb_padded)],
        "col": [None] + [(i, 0) if i < nb else None
                         for i in range(1, nb_padded)],
    }
    if banded:
        coords["lo"] = [None, None] + [(i, i - 1) if i < nb else None
                                       for i in range(2, nb_padded)]
        coords["hi"] = [None] + [(i, i + 1) if i + 1 < nb else None
                                 for i in range(1, nb_padded)]
    return coords


def arrow_blocks_streamed(matrix: CsrLike, width: int, mesh,
                          axis: str = "blocks",
                          n_blocks: Optional[int] = None,
                          pad_blocks_to: Optional[int] = None,
                          banded: bool = False,
                          dtype=np.float32,
                          check: bool = True,
                          fmt: str = "ell",
                          head_fmt: str = "auto",
                          binary="auto") -> ArrowBlocks:
    """Streaming twin of ``arrow_blocks_from_csr`` for >RAM matrices.

    Never materializes a whole level on the host: a first streaming
    pass over the (possibly memmapped) matrix sizes the shared ELL slot
    budgets block by block; the device arrays are then created with
    ``jax.make_array_from_callback``, whose callback packs only the
    block-rows of one addressable shard — peak host RSS is
    O(one shard) = O(level / n_devices) plus memmap page cache, the
    TPU analog of the reference's root-reads-and-ships-per-rank loader
    (reference arrow_dec_mpi.py:629-887, graphio.py:449-495).

    Produces bit-identical arrays to the eager builder (tested).
    """
    from jax.sharding import NamedSharding, PartitionSpec as P

    from arrow_matrix_tpu.ops.ell import SLOT_ALIGN, align_up

    if fmt not in ("ell", "dense"):
        raise ValueError(f"unknown block format {fmt!r}")
    nb = n_blocks if n_blocks is not None else number_of_blocks(matrix, width)
    nb_padded = max(pad_blocks_to or nb, nb)
    coords = _stack_coords(nb, nb_padded, banded)
    is_binary = resolve_blocks_binary(matrix, fmt, binary)
    from arrow_matrix_tpu.ops.ell import block_index_dtype

    idt = block_index_dtype(width)

    host_dtype = scipy_safe_dtype(dtype)

    def blk(ij):
        i, j = ij
        return load_block(matrix, i * width, (i + 1) * width,
                          j * width, (j + 1) * width, width,
                          dtype=host_dtype)

    # Pass 1 — streaming slot sizing + nnz-capture check (each block is
    # loaded, reduced to its max row count, and dropped).
    slots: dict[str, int] = {}
    captured = 0
    head_nnz_max = 0
    head_row_max = 0
    for name, cs in coords.items():
        need = 0
        for ij in cs:
            if ij is None:
                continue
            b = blk(ij)
            captured += b.nnz
            counts = np.diff(b.indptr)
            if counts.size:
                need = max(need, int(counts.max()))
            if name == "head":
                head_nnz_max = max(head_nnz_max, int(b.nnz))
        if name == "head":
            head_row_max = need
        slots[name] = align_up(need, SLOT_ALIGN) if need else 0

    # Flat-COO head decision (the SAME rule as the eager builder, via
    # the shared helper — the builders must agree bit-for-bit).
    head_flat = fmt == "ell" and choose_flat_head_from_stats(
        nb_padded, width, head_row_max, head_nnz_max, dtype, head_fmt)
    head_budget = align_up(head_nnz_max, SLOT_ALIGN) if head_nnz_max else 0

    if check:
        total = num_nonzeros(matrix)
        if captured != total:
            raise ValueError(
                f"arrow tiling captured {captured} of {total} nonzeros: "
                f"the matrix has entries outside the "
                f"{'banded' if banded else 'block-diagonal'} arrow "
                f"pattern at width {width} / {nb} blocks")

    # Pass 2 — per-device-shard packing: pack one shard's block range,
    # ship it to its device, free the host buffer, move on.  Peak host
    # RSS is one shard's (cols, data) pair; the global arrays are then
    # assembled from the per-device pieces without further host copies.
    sharding = NamedSharding(mesh, P(axis))

    def pack_shard(name: str, sl: slice):
        cs = coords[name][sl]
        m = slots[name]
        if name == "head" and head_flat:
            from arrow_matrix_tpu.ops.ell import csr_flat_pack

            rows = np.full((len(cs), head_budget), width, dtype=idt)
            cols = np.zeros((len(cs), head_budget), dtype=idt)
            data = np.zeros((len(cs), head_budget), dtype=dtype)
            for r_i, ij in enumerate(cs):
                if ij is None:
                    continue
                b = blk(ij)
                if b.nnz:
                    rows[r_i], cols[r_i], data[r_i] = csr_flat_pack(
                        b, pad_to=head_budget, dtype=dtype,
                        index_dtype=idt)
            if is_binary:
                return rows, cols        # values never needed (dummy-row)
            return rows, cols, data
        if fmt == "dense":
            cols = np.zeros((len(cs), 0, 0), dtype=np.int32)
            data = np.zeros((len(cs), width, width), dtype=dtype)
            for r, ij in enumerate(cs):
                if ij is not None:
                    data[r] = blk(ij).toarray()
        else:
            from arrow_matrix_tpu.ops.ell import ell_pack

            cols = np.zeros((len(cs), width, m), dtype=idt)
            data = (None if is_binary
                    else np.zeros((len(cs), width, m), dtype=dtype))
            deg = np.zeros((len(cs), width), dtype=np.int32)
            for r, ij in enumerate(cs):
                if ij is None:
                    continue
                b = blk(ij)
                if b.nnz:
                    c_r, d_r = ell_pack(b, max_nnz=m, dtype=dtype,
                                        with_data=not is_binary,
                                        index_dtype=idt)
                    cols[r] = c_r
                    if is_binary:
                        deg[r] = np.diff(b.tocsr().indptr).astype(np.int32)
                    else:
                        data[r] = d_r
            if is_binary:
                return cols, deg
        return cols, data

    def make_stack(name: str):
        m = slots[name]
        if name == "head" and head_flat:
            shapes = ([(nb_padded, head_budget)] * 2 if is_binary
                      else [(nb_padded, head_budget)] * 3)
        elif fmt == "dense":
            shapes = [(nb_padded, 0, 0), (nb_padded, width, width)]
        elif is_binary:
            shapes = [(nb_padded, width, m), (nb_padded, width)]
        else:
            shapes = [(nb_padded, width, m)] * 2
        dev_map = sharding.addressable_devices_indices_map(shapes[-1])
        parts: list[list] = [[] for _ in shapes]
        for dev, idx in dev_map.items():
            arrs = pack_shard(name, idx[0])
            for p, a in zip(parts, arrs):
                p.append(jax.device_put(a, dev))
            del arrs  # host buffers freed before the next shard packs
        return tuple(
            jax.make_array_from_single_device_arrays(shape, sharding, p)
            for shape, p in zip(shapes, parts))

    kw = {}
    for name in coords:
        out = make_stack(name)
        if name == "head" and head_flat:
            if is_binary:
                kw["head_rows"], kw["head_cols"] = out
            else:
                kw["head_rows"], kw["head_cols"], kw["head_data"] = out
        elif fmt != "dense" and is_binary:
            kw[f"{name}_cols"], kw[f"{name}_deg"] = out
        else:
            kw[f"{name}_cols"], kw[f"{name}_data"] = out
    return ArrowBlocks(width=width, n_blocks=nb_padded, banded=banded,
                       fmt=fmt, head_flat=head_flat, **kw)


def block_spmm(fmt: str, cols: jax.Array, data: Optional[jax.Array],
               x: jax.Array, chunk: Optional[int] = None,
               deg: Optional[jax.Array] = None) -> jax.Array:
    """Batched per-block SpMM dispatching on the block format.

    cols/data: stacked blocks (b, ...); x: (b, w, k) -> (b, w, k).
    Binary ELL stacks pass data=None with deg (b, w).
    """
    if fmt == "dense":
        return dense_spmm_batched(data, x)
    return ell_spmm_batched(cols, data, x, chunk=chunk, deg=deg)


def head_block_spmm(blocks: ArrowBlocks, x: jax.Array,
                    chunk: Optional[int] = None) -> jax.Array:
    """Per-block head-row contributions: block j's A_0j @ X_j, shape
    (nb, w, k).  Sum (or psum) over the block axis gives C_0.

    Branches on the head storage: flat-COO heads (head_flat) scatter-add
    per block — O(nnz) compute immune to the head rows' degree skew —
    ELL/dense heads go through ``block_spmm``.  Works identically on
    global arrays and on per-shard slices under shard_map.
    """
    if blocks.head_gell:
        raise ValueError(
            "gell heads gather from the whole feature array and have no "
            "per-block form; they do not shard — use head_fmt='flat' or "
            "'ell' on a mesh (arrow_spmm handles gell directly)")
    if blocks.head_flat:
        from arrow_matrix_tpu.ops.ell import csr_flat_spmm

        w = blocks.width
        if blocks.head_data is None:   # binary: no values needed at all
            return jax.vmap(
                lambda r, c, xx: csr_flat_spmm(r, c, None, xx, w))(
                    blocks.head_rows, blocks.head_cols, x)
        return jax.vmap(
            lambda r, c, d, xx: csr_flat_spmm(r, c, d, xx, w))(
                blocks.head_rows, blocks.head_cols, blocks.head_data, x)
    return block_spmm(blocks.fmt, blocks.head_cols, blocks.head_data, x,
                      chunk=chunk, deg=blocks.head_deg)


def block_spmm_shared(fmt: str, cols: jax.Array, data: Optional[jax.Array],
                      x0: jax.Array, chunk: Optional[int] = None,
                      deg: Optional[jax.Array] = None) -> jax.Array:
    """Batched per-block SpMM against one shared operand (X_0):
    (b, ...) blocks x (w, k) -> (b, w, k)."""
    if fmt == "dense":
        return jnp.einsum("bri,ik->brk", data, x0,
                          preferred_element_type=jnp.float32).astype(x0.dtype)
    if data is None:
        return jax.vmap(
            lambda cc, dg: ell_spmm(cc, None, x0, chunk=chunk, deg=dg))(
                cols, deg)
    return jax.vmap(lambda cc, dd: ell_spmm(cc, dd, x0, chunk=chunk))(
        cols, data)


def arrow_spmm(blocks: ArrowBlocks, x: jax.Array,
               chunk: Optional[int] = None) -> jax.Array:
    """Single-device arrow SpMM: x is (nb, w, k) blocked like the rows.

    Jittable; this is the whole per-iteration compute of the slim layout
    on one chip.  The distributed version in
    ``arrow_matrix_tpu.parallel.arrow_layout`` applies the same block
    compute per shard with psum/ppermute supplying C_0 / X_0 / halos.
    """
    nb, w, k = x.shape
    assert nb == blocks.n_blocks and w == blocks.width

    if blocks.head_gell:
        # One gather+reduce over the flat feature array (w output rows
        # only): the TPU-native head kernel — no scatter, MXU-friendly
        # weighted reduction, chunked like every other ELL stack.
        c0 = ell_spmm(blocks.head_cols, blocks.head_data,
                      x.reshape(nb * w, k), chunk=chunk,
                      deg=blocks.head_deg)
    else:
        c0 = head_block_spmm(blocks, x, chunk=chunk).sum(axis=0)

    c = block_spmm(blocks.fmt, blocks.diag_cols, blocks.diag_data, x,
                   chunk=chunk, deg=blocks.diag_deg)
    c = c + block_spmm_shared(blocks.fmt, blocks.col_cols, blocks.col_data,
                              x[0], chunk=chunk, deg=blocks.col_deg)

    if blocks.banded:
        zeros = jnp.zeros((1, w, k), dtype=x.dtype)
        x_lo = jnp.concatenate([zeros, x[:-1]], axis=0)   # block i sees X_{i-1}
        x_hi = jnp.concatenate([x[1:], zeros], axis=0)    # block i sees X_{i+1}
        c = c + block_spmm(blocks.fmt, blocks.lo_cols, blocks.lo_data, x_lo,
                           chunk=chunk, deg=blocks.lo_deg)
        c = c + block_spmm(blocks.fmt, blocks.hi_cols, blocks.hi_data, x_hi,
                           chunk=chunk, deg=blocks.hi_deg)

    return c.at[0].set(c0)


def block_features(x: np.ndarray, width: int, n_blocks: int) -> np.ndarray:
    """Host helper: pad (n, k) features with zero rows and reshape to the
    blocked (nb, w, k) device layout."""
    n, k = x.shape
    total = width * n_blocks
    if n > total:
        x = x[:total]
    elif n < total:
        x = np.pad(x, ((0, total - n), (0, 0)))
    return x.reshape(n_blocks, width, k)


def unblock_features(x: jax.Array | np.ndarray, n: int) -> np.ndarray:
    """Inverse of block_features: (nb, w, k) -> (n, k)."""
    arr = np.asarray(x)
    return arr.reshape(-1, arr.shape[-1])[:n]


def block_row_stats(blocks: ArrowBlocks) -> dict:
    """Per-block-row (rows, nnz, slots) over the padded block grid — the
    arrow layout's compute units, which the obs layer summarizes into
    the paper's max/mean imbalance bound (obs/imbalance.py).

    Every off-head stack entry i lives on block row i (``_stack_coords``:
    diag (i,i), col (i,0), lo (i,i-1), hi (i,i+1)); structurally-empty
    slots are zero-filled and contribute nothing.  All head blocks land
    on block row 0, whatever the head packing (ELL stack / flat-COO /
    global-row ELL).
    """
    from arrow_matrix_tpu.ops.ell import ell_slot_stats, flat_slot_stats

    nb = blocks.n_blocks
    nnz = np.zeros(nb, dtype=np.int64)
    slots = np.zeros(nb, dtype=np.int64)

    def stack_stats(cols, data, deg):
        if cols is None and data is None:
            return None
        # Dense blocks carry EMPTY cols arrays (not None) next to the
        # (nb, w, w) value stacks; detect them by the value stack being
        # the larger array.
        dense = (cols is None
                 or (data is not None
                     and np.asarray(cols).size < getattr(data, "size", 0)))
        if dense:
            # Dense (nb, w, w) blocks: resident slots are every value.
            d = np.asarray(data)
            e_nnz = np.count_nonzero(
                d.reshape(d.shape[0], -1), axis=1).astype(np.int64)
            e_slots = np.full(
                d.shape[0],
                int(np.prod(d.shape[1:], dtype=np.int64)),
                dtype=np.int64)
            return e_nnz, e_slots
        return ell_slot_stats(cols, data, deg)

    for name in ("diag", "col", "lo", "hi"):
        st = stack_stats(getattr(blocks, f"{name}_cols"),
                         getattr(blocks, f"{name}_data"),
                         getattr(blocks, f"{name}_deg"))
        if st is None:
            continue
        e_nnz, e_slots = st
        n = min(len(e_nnz), nb)
        nnz[:n] += e_nnz[:n]
        slots[:n] += e_slots[:n]

    if blocks.head_flat:
        # Flat-COO head: padding entries point at the dummy row
        # == width.
        h_nnz, h_slots = flat_slot_stats(blocks.head_rows, blocks.width)
        nnz[0] += int(h_nnz.sum())
        slots[0] += int(h_slots.sum())
    elif blocks.head_gell:
        cols = np.asarray(blocks.head_cols)
        slots[0] += int(cols.size)
        if blocks.head_deg is not None:
            nnz[0] += int(np.asarray(blocks.head_deg).sum())
        elif blocks.head_data is not None:
            nnz[0] += int(np.count_nonzero(np.asarray(blocks.head_data)))
        else:
            nnz[0] += int(cols.size)
    else:
        st = stack_stats(blocks.head_cols, blocks.head_data,
                         blocks.head_deg)
        if st is not None:
            e_nnz, e_slots = st
            nnz[0] += int(e_nnz.sum())
            slots[0] += int(e_slots.sum())

    rows = np.full(nb, blocks.width, dtype=np.int64)
    return {"rows": rows, "nnz": nnz, "slots": slots}
