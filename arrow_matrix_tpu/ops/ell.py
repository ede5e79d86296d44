"""ELL-packed sparse blocks and the core SpMM kernels.

TPU asks for static shapes and vectorizable access patterns; CSR's ragged
rows are hostile to both.  The framework's device-side sparse format is
therefore ELL: each row padded to a fixed slot count ``m`` with column
indices (padding slots point at column 0 with value 0):

    cols: (rows, m) int32      data: (rows, m) dtype

SpMM is then a gather + weighted reduction,
``out[r] = sum_j data[r, j] * x[cols[r, j]]``, which XLA lowers to
row-gathers from a dense operand that stays in VMEM for arrow-block
sizes.  Slot chunking bounds the materialized gather to
``rows * chunk * k`` (the TPU analog of the reference's k-dimension GPU
tiling, reference arrow/baseline/spmm_petsc.py:323-395).

This replaces the reference's scipy-CSR ``@`` (CPU) and cupy/cuSPARSE
CSRMM (GPU) device kernels (reference arrow/common/sp2cp.py:6-16 and the
``*_gpu`` methods) — with the data resident in HBM across iterations
instead of being re-uploaded per call (a known reference inefficiency,
arrow/arrow_mpi.py:314).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from scipy import sparse

# Gather chunks, the packed gather's tier rows and the slot counts of
# the arrow-block ELLs are multiples of this (one sublane tile: the
# second-minor dimension of a TPU array pads to it).  The fold's tiers
# hold exact degrees: a padded slot is gathered like a real one.
SLOT_ALIGN = 8
# Width of a TPU vector register and of the minor tile dimension: a
# gathered row narrower than this costs about as much as a whole one.
LANES = 128


def align_up(x: int, align: int) -> int:
    return -(-x // align) * align


def lane_pack_factor(k: int) -> int:
    """Nodes per 128-lane packed row of :func:`lane_pack`: ``128 // k``
    when k features are narrower than the lane tile and divide it, else
    1 (the packed form does not engage)."""
    return LANES // k if 0 < k < LANES and LANES % k == 0 else 1


def lane_pack(x_t: jax.Array) -> Optional[jax.Array]:
    """Feature-major ``(k, N)`` -> row-major ``(ceil(N / p), 128)`` with
    ``p = lane_pack_factor(k)``: row ``j // p``, lanes
    ``k*(j % p) .. k*(j % p) + k - 1`` hold node j's k features (N is
    zero-padded up to a multiple of p).  None when p is 1: the k-wide
    rows of ``x_t`` are gathered as they are."""
    k, n = x_t.shape
    p = lane_pack_factor(k)
    if p == 1:
        return None
    pad = align_up(n, p) - n
    if pad:
        x_t = jnp.pad(x_t, ((0, 0), (0, pad)))
    return x_t.T.reshape(-1, LANES)


def block_index_dtype(width: int):
    """Index dtype for block-LOCAL columns/rows: int16 halves the
    streamed index bytes whenever every representable value (columns
    < width, plus the flat head's dummy row == width) fits."""
    return np.int16 if width < np.iinfo(np.int16).max else np.int32


def ell_pack(m: sparse.spmatrix, max_nnz: Optional[int] = None,
             dtype=np.float32, with_data: bool = True,
             index_dtype=np.int32
             ) -> tuple[np.ndarray, Optional[np.ndarray]]:
    """Pack a scipy sparse matrix into (cols, data) ELL arrays.

    Vectorized fill: O(nnz) numpy work, no per-row Python loop (matters
    at the 100M-row scale this framework targets).  ``with_data=False``
    skips the value array entirely (binary layouts need only cols —
    allocating and discarding the values would double packing work).
    ``index_dtype`` shrinks the column indices (block-LOCAL indices fit
    int16 up to width 32767 — half the index bytes; see
    ``block_index_dtype``).
    """
    csr = m.tocsr()
    csr.sum_duplicates()
    csr.sort_indices()
    counts = np.diff(csr.indptr)
    need = int(counts.max()) if counts.size and counts.max() > 0 else 0
    if max_nnz is None:
        max_nnz = need
    if need > max_nnz:
        raise ValueError(f"row has {need} nnz > max_nnz={max_nnz}")
    rows = csr.shape[0]
    cols = np.zeros((rows, max_nnz), dtype=index_dtype)
    data = np.zeros((rows, max_nnz), dtype=dtype) if with_data else None
    if csr.nnz:
        slot = np.arange(csr.nnz) - np.repeat(csr.indptr[:-1], counts)
        row = np.repeat(np.arange(rows), counts)
        cols[row, slot] = csr.indices
        if with_data:
            data[row, slot] = csr.data
    return cols, data


def ell_pack_stack(mats: list[sparse.spmatrix], dtype=np.float32,
                   align: int = SLOT_ALIGN,
                   rows: Optional[int] = None,
                   index_dtype=np.int32) -> tuple[np.ndarray, np.ndarray]:
    """Pack a list of equal-shaped sparse blocks into stacked ELL arrays
    (b, rows, m) with one shared slot count m (max over blocks, aligned).

    Empty list entries (None) become all-zero blocks; an all-None list is
    allowed when ``rows`` is given (zero-slot arrays).
    """
    shapes = [m.shape for m in mats if m is not None]
    if not shapes and rows is None:
        raise ValueError("no non-empty blocks and no explicit row count")
    rows = rows if rows is not None else shapes[0][0]
    need = 0
    for m in mats:
        if m is None:
            continue
        counts = np.diff(m.tocsr().indptr)
        if counts.size:
            need = max(need, int(counts.max()))
    m_slots = align_up(need, align) if need else 0
    cols = np.zeros((len(mats), rows, m_slots), dtype=index_dtype)
    data = np.zeros((len(mats), rows, m_slots), dtype=dtype)
    for i, m in enumerate(mats):
        if m is None or m.nnz == 0:
            continue
        c, d = ell_pack(m, max_nnz=m_slots, dtype=dtype,
                        index_dtype=index_dtype)
        cols[i] = c
        data[i] = d
    return cols, data


def feature_major_chunk(rows: int, k: int, m: int, budget_bytes: int,
                        itemsize: int = 4) -> Optional[int]:
    """Slot chunk bounding the gather intermediate of :func:`ell_spmm_t`
    (``chunk * rows`` gathered rows) to ``budget_bytes``; ``None`` when
    the whole slot axis fits.

    Each gathered row is budgeted at its physical width: 128 lanes
    whenever the lane-packed form runs (``lane_pack_factor(k) > 1``),
    else k, so a packed tier chunks exactly as the same tier does at
    k=128.  Chunks are whole multiples of SLOT_ALIGN; below one such
    tile the chunk is a single slot (chunk 1, a 2-D gather), the only
    bound left when one tile of slots is already over budget (the
    largest tier of a 2^22-row BA fold, 2.37M rows at 128 lanes: 9.7 GB
    per tile).
    """
    if m == 0 or rows <= 0 or k <= 0:
        return None
    width = LANES if lane_pack_factor(k) > 1 else k
    per_slot = width * rows * itemsize
    if align_up(m, SLOT_ALIGN) * per_slot <= budget_bytes:
        return None
    c = int(budget_bytes // per_slot)
    c -= c % SLOT_ALIGN
    if c < SLOT_ALIGN:
        return 1
    return None if c >= m else c


def slot_runs(m: int, chunk: Optional[int]) -> list[tuple[int, int, int]]:
    """How :func:`ell_spmm_t` walks ``m`` slots under ``chunk``: runs
    ``(lo, hi, c)``, slots ``lo .. hi - 1`` gathered ``c`` at a time.
    The whole chunks come first; when ``chunk`` does not divide ``m``
    the last ``m % chunk`` slots follow one at a time, as 2-D gathers,
    so that no padded slot is gathered."""
    if m == 0:
        return []
    c = m if chunk is None else min(chunk, m)
    whole = m - m % c
    if whole == m:
        return [(0, m, c)]
    return [(0, whole, c), (whole, m, 1)]


def auto_chunk(rows: int, k: int, m: int, budget_bytes: int,
               itemsize: int = 4,
               lanes: Optional[int] = None) -> Optional[int]:
    """Slot-chunk size bounding the ELL gather intermediate
    (``rows × chunk × k`` elements) to ``budget_bytes``; ``None`` when
    the whole slot axis fits.  The auto-sizing counterpart of the
    reference's OOM-model GPU tiling
    (reference arrow/baseline/spmm_petsc.py:323-395) — derive
    ``budget_bytes`` from the live chip via
    ``utils.platform.device_memory_budget``.

    The budget is enforced against the intermediate's PHYSICAL bytes:
    on TPU its minor dimension k pads to the 128-lane tile (the
    layout-padding law, PERFORMANCE.md), so a k=16 temp occupies 8x its
    logical size and the chunk must shrink accordingly.  ``lanes``
    overrides the detected lane width (1 = no padding).
    """
    if m == 0 or rows <= 0 or k <= 0:
        return None
    if lanes is None:
        import jax

        lanes = 128 if jax.default_backend() == "tpu" else 1
    k_phys = max(k, lanes)
    if rows * m * k_phys * itemsize <= budget_bytes:
        return None
    per_slot = rows * k_phys * itemsize
    # Align DOWN so the chunked intermediate stays under budget; the
    # SLOT_ALIGN floor is the one allowed overshoot (a narrower chunk
    # cannot be tiled).
    c = int(budget_bytes // per_slot)
    c = max(c - c % SLOT_ALIGN, SLOT_ALIGN)
    return None if c >= m else c


def ell_spmm(cols: jax.Array, data: Optional[jax.Array], x: jax.Array,
             chunk: Optional[int] = None,
             deg: Optional[jax.Array] = None) -> jax.Array:
    """out[r] = sum_j data[r, j] * x[cols[r, j], :].

    Binary mode (implicit-ones matrices — graph adjacency): pass
    ``data=None`` and ``deg`` instead; the slot-validity mask is an
    iota-vs-degree compare generated in registers, so the value
    array's bytes vanish (half the streamed slot bytes).  Bit-identical
    to the weighted kernel on 0/1 data.

    :param cols: (rows, m) integer column indices (int32, or int16 from
        the block packers at width < 32767), 0 for padding.
    :param data: (rows, m) values, 0 for padding; or None for binary.
    :param deg:  (rows,) int32 valid-slot counts (binary mode only).
    :param x:    (n_cols, k)     — dense operand.
    :param chunk: slot-axis chunk size bounding the gather intermediate;
        None processes all slots at once.
    """
    rows, m = cols.shape
    k = x.shape[-1]
    if data is None and deg is None and m > 0:
        raise ValueError("binary ELL (data=None) requires deg")
    if m == 0:
        return jnp.zeros((rows, k), dtype=x.dtype)
    if chunk is None or chunk >= m:
        w = (data if data is not None
             else (jnp.arange(m, dtype=deg.dtype)[None, :]
                   < deg[:, None]).astype(jnp.float32))
        gathered = jnp.take(x, cols, axis=0)          # (rows, m, k)
        return jnp.einsum("rm,rmk->rk", w, gathered,
                          preferred_element_type=jnp.float32).astype(x.dtype)

    n_chunks = align_up(m, chunk) // chunk
    pad = n_chunks * chunk - m
    if pad:
        cols = jnp.pad(cols, ((0, 0), (0, pad)))
        if data is not None:
            data = jnp.pad(data, ((0, 0), (0, pad)))
    cols_c = cols.reshape(rows, n_chunks, chunk).transpose(1, 0, 2)

    def contribution(c, w):
        gathered = jnp.take(x, c, axis=0)             # (rows, chunk, k)
        return jnp.einsum("rm,rmk->rk", w, gathered,
                          preferred_element_type=jnp.float32)

    if data is not None:
        data_c = data.reshape(rows, n_chunks, chunk).transpose(1, 0, 2)

        def body(acc, cd):
            c, d = cd
            return acc + contribution(c, d), None
        xs = (cols_c, data_c)
    else:
        offsets = jnp.arange(n_chunks, dtype=deg.dtype) * chunk

        def body(acc, co):
            c, off = co
            w = (off + jnp.arange(chunk, dtype=deg.dtype)[None, :]
                 < deg[:, None]).astype(jnp.float32)
            return acc + contribution(c, w), None
        xs = (cols_c, offsets)

    acc0 = jnp.zeros((rows, k), dtype=jnp.float32)
    acc, _ = jax.lax.scan(body, acc0, xs)
    return acc.astype(x.dtype)


def _packed_gather(packed: jax.Array, k: int,
                   cols_c: jax.Array) -> jax.Array:
    """``x_t[:, cols_c]`` as a ``(k, *cols_c.shape)`` array, gathered
    as whole 128-lane rows of :func:`lane_pack`'s operand: each slot
    keeps the k lanes of its own node and zeroes the rest, and the 0/1
    matrix ``S[k*g + f, f] = 1`` folds the p lane groups onto the k
    features on the MXU.  Each output is one value plus zeros, and
    ``HIGHEST`` keeps that value whole (a default-precision pass would
    round f32 to bf16), so the result is the k-wide gather's, bit for
    bit, while the features are finite (an inf times a zero of ``S`` is
    NaN).  The weights are applied after, as in the k-wide form."""
    p = LANES // k
    g = jnp.take(packed, cols_c // p, axis=0)             # (..., 128)
    own = (jnp.arange(LANES, dtype=cols_c.dtype) // k
           == (cols_c % p)[..., None])
    select = np.tile(np.eye(k, dtype=np.float32), (p, 1))
    return jnp.einsum("...l,lf->f...", jnp.where(own, g, 0), select,
                      precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32
                      ).astype(packed.dtype)


def ell_spmm_t(cols: jax.Array, x_t: jax.Array,
               data: Optional[jax.Array] = None,
               deg: Optional[jax.Array] = None,
               chunk: Optional[int] = None,
               packed: Optional[jax.Array] = None) -> jax.Array:
    """Slot-major, feature-major ELL SpMM (the padding-free layout):
    ``out_t[:, r] = sum_j w[j, r] * x_t[:, cols[j, r]]``.

    Motivation (measured, v5e): XLA's TPU layout tiles the last two
    dims to (8, 128), so a row-major ELL array ``(rows, m)`` with
    m = 8..24 slots is *physically* padded 5-16x in HBM, and a row-major
    feature array ``(N, 16)`` 8x — a compile-time OOM at protocol scale
    (28 GB program for 2.4 GB of logical data) and the same factor in
    streamed bytes.  Storing slots major ``(m, rows)`` and features
    major ``(k, N)`` puts the large dimension minor in every stored
    array.

    The gather itself fetches one row per slot from a row-major view
    of the features, and on TPU a row narrower than 128 lanes costs
    about as much as a whole one (v5e, 2^22-row BA fold: each k=16
    index took 2.0-2.5x the time of a k=128 one).  So when k divides
    128 (``lane_pack_factor(k) = p > 1``) the operand is packed p nodes
    to a 128-lane row (:func:`lane_pack`), each slot gathers the whole
    row ``packed[col // p]``, and the node's own k lanes are selected
    exactly (:func:`_packed_gather`).  The packed form pads the
    rows to a multiple of SLOT_ALIGN, so that the ``(chunk * rows,
    128)`` gather reshapes to ``(chunk, rows, 128)`` without a
    relayout.  k >= 128, or k not dividing 128, gathers k-wide rows of
    ``x_t``.

    Weighted mode passes ``data`` (m, rows) with zeros in padding
    slots.  Binary mode (implicit-ones matrices — graph adjacency)
    passes ``data=None`` and ``deg`` (rows,) instead: the slot-validity
    mask is an iota-vs-degree compare generated in registers, so the
    value array's bytes vanish entirely.  Bit-identical to the weighted
    kernel on 0/1 data (same addends, same slot order).

    :param cols: (m, rows) integer column indices (any int dtype), 0 in
        padding slots.
    :param x_t:  (k, n_cols) — dense operand, feature-major.
    :param data: (m, rows) values, or None for binary.
    :param deg:  (rows,) int32 valid-slot counts (binary mode only).
    :param chunk: slot-axis chunk bounding the gather intermediate
        (chunk * rows gathered rows, see :func:`feature_major_chunk`);
        None processes all slots at once.  When it does not divide m,
        the slots past the last whole chunk are gathered one at a time
        after the chunks (:func:`slot_runs`): nothing is padded.
    :param packed: ``lane_pack(x_t)``, for callers that run several
        tiers over one operand; built here when not given.
    :returns: (k, rows) result, feature-major.
    """
    m, rows = cols.shape
    k = x_t.shape[0]
    if data is None and deg is None and m > 0:
        raise ValueError("binary ELL (data=None) requires deg")
    if m == 0:
        return jnp.zeros((k, rows), dtype=x_t.dtype)
    rows_out = rows
    if packed is None:
        packed = lane_pack(x_t)
    if packed is not None:
        rows = align_up(rows, SLOT_ALIGN)
        if rows > rows_out:
            cols = jnp.pad(cols, ((0, 0), (0, rows - rows_out)))
            if data is not None:
                data = jnp.pad(data, ((0, 0), (0, rows - rows_out)))
            else:
                deg = jnp.pad(deg, (0, rows - rows_out))
    def gather(cols_c):
        """``x_t[:, cols_c]``: (k, *cols_c.shape) in x_t's dtype."""
        if packed is not None:
            return _packed_gather(packed, k, cols_c)
        if cols_c.ndim == 1:
            return jnp.take(x_t, cols_c, axis=1)
        return jnp.take(x_t, cols_c.reshape(-1), axis=1).reshape(
            (k,) + cols_c.shape)

    def contribution(cols_c, w_c, c):
        if c == 1:
            # One slot per step: a 2-D (k, rows) gather.  A (k, 1, rows)
            # intermediate would pad its slot axis to a whole sublane
            # tile on TPU — 8x the bytes this chunking exists to bound.
            return (gather(cols_c[0]) * w_c[0][None]).astype(jnp.float32)
        g = gather(cols_c)
        # f32 accumulation whatever the carried feature dtype: bf16
        # features (half the gathered bytes — the k=128 bandwidth
        # lever) must not also mean bf16 sums, and f32 matrix VALUES
        # must not demote — jnp promotion makes bf16*f32 -> f32 (a
        # bool binary mask promotes to g's dtype, exact either way).
        # The carried result still rounds to x_t.dtype at tier/level
        # boundaries — inherent to a bf16 carriage, documented in
        # resolve_feature_dtype.
        return (g * w_c[None]).sum(axis=1, dtype=jnp.float32)

    def run_sum(lo, hi, c):
        """f32 (k, rows): the sum over slots lo..hi-1, in chunks of c."""
        n_chunks = (hi - lo) // c
        cols_r, data_r = cols, data
        if (lo, hi) != (0, m):
            cols_r = cols[lo:hi]
            data_r = None if data is None else data[lo:hi]
        if n_chunks == 1:
            if data_r is not None:
                w = data_r
            else:
                w = (jnp.arange(lo, hi, dtype=deg.dtype)[:, None]
                     < deg[None, :])
            return contribution(cols_r, w, c)
        cols_c = cols_r.reshape(n_chunks, c, rows)
        if data_r is not None:
            def body(acc, xs):
                cc, dc = xs
                return acc + contribution(cc, dc, c), None
            xs = (cols_c, data_r.reshape(n_chunks, c, rows))
        else:
            offsets = jnp.arange(n_chunks, dtype=deg.dtype) * c
            if lo:
                offsets = offsets + lo

            def body(acc, xs):
                cc, off = xs
                w = (off + jnp.arange(c, dtype=deg.dtype)[:, None]
                     < deg[None, :])
                return acc + contribution(cc, w, c), None
            xs = (cols_c, offsets)

        acc0 = jnp.zeros((k, rows), dtype=jnp.float32)
        acc, _ = jax.lax.scan(body, acc0, xs)
        return acc

    acc = None
    for lo, hi, c in slot_runs(m, chunk):
        part = run_sum(lo, hi, c)
        acc = part if acc is None else acc + part
    if rows > rows_out:
        acc = acc[:, :rows_out]
    return acc.astype(x_t.dtype)


def ell_spmm_batched(cols: jax.Array, data: Optional[jax.Array],
                     x: jax.Array, chunk: Optional[int] = None,
                     deg: Optional[jax.Array] = None) -> jax.Array:
    """Batched ELL SpMM over stacked blocks.

    cols/data: (b, rows, m); x: (b, n_cols, k) -> (b, rows, k).
    Binary mode: data=None with deg (b, rows) degree stacks.
    """
    if data is None:
        return jax.vmap(
            lambda c, dg, xx: ell_spmm(c, None, xx, chunk=chunk, deg=dg))(
                cols, deg, x)
    return jax.vmap(lambda c, d, xx: ell_spmm(c, d, xx, chunk=chunk))(
        cols, data, x)


def dense_pack_stack(mats: list[sparse.spmatrix], dtype=np.float32,
                     rows: Optional[int] = None) -> np.ndarray:
    """Pack sparse blocks into one dense (b, rows, rows) array.

    The MXU-native block format: an arrow matrix has only ~3 structural
    blocks per block-row, so densifying costs 3·n·w memory for an n-row
    decomposition at width w — affordable up to mid-size widths, and the
    SpMM becomes batched dense matmuls at full systolic-array throughput
    (the gather-based ELL path wins only when w is too large to densify).
    """
    shapes = [m.shape for m in mats if m is not None]
    if not shapes and rows is None:
        raise ValueError("no non-empty blocks and no explicit row count")
    rows = rows if rows is not None else shapes[0][0]
    out = np.zeros((len(mats), rows, rows), dtype=dtype)
    for i, m in enumerate(mats):
        if m is None or m.nnz == 0:
            continue
        # scipy cannot densify extension dtypes (a bf16 CSR raises in
        # csr_todense even targeting bf16); densify at f32 and round.
        if m.dtype.kind not in "fiub":
            m = m.astype(np.float32)
        out[i] = m.toarray().astype(dtype)
    return out


def dense_spmm_batched(data: jax.Array, x: jax.Array) -> jax.Array:
    """Batched dense block SpMM: (b, w, w) @ (b, w, k) -> (b, w, k),
    f32 accumulation on the MXU regardless of storage dtype."""
    return jnp.einsum("bri,bik->brk", data, x,
                      preferred_element_type=jnp.float32).astype(x.dtype)


def csr_flat_pack(m: sparse.spmatrix, pad_to: Optional[int] = None,
                  dtype=np.float32,
                  align: int = SLOT_ALIGN,
                  index_dtype=np.int32) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Flat COO-style packing (rows, cols, data) sorted by row, padded to a
    static nnz budget.  Padding entries use row=rows (scatter-dropped) and
    col=0.  Suits blocks with skewed row degrees where ELL padding blows
    up (the arrow head rows)."""
    coo = m.tocoo()
    order = np.argsort(coo.row, kind="stable")
    r = coo.row[order].astype(index_dtype)
    c = coo.col[order].astype(index_dtype)
    d = coo.data[order].astype(dtype)
    nnz = r.size
    budget = pad_to if pad_to is not None else align_up(max(nnz, 1), align)
    if nnz > budget:
        raise ValueError(f"nnz {nnz} exceeds budget {budget}")
    rows_pad = np.full(budget, m.shape[0], dtype=index_dtype)
    cols_pad = np.zeros(budget, dtype=index_dtype)
    data_pad = np.zeros(budget, dtype=dtype)
    rows_pad[:nnz] = r
    cols_pad[:nnz] = c
    data_pad[:nnz] = d
    return rows_pad, cols_pad, data_pad


def flat_pack_stack(mats: list[sparse.spmatrix], dtype=np.float32,
                    align: int = SLOT_ALIGN, rows: Optional[int] = None,
                    index_dtype=np.int32
                    ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Pack equal-shaped sparse blocks into stacked flat-COO arrays
    (b, B) with one shared per-block nnz budget B (max over blocks,
    aligned).  Padding entries point at the dummy row ``rows`` (dropped
    by the csr_flat_spmm scatter).  O(nnz) storage regardless of row
    skew — the arrow-head companion of ``ell_pack_stack``."""
    shapes = [m.shape for m in mats if m is not None]
    if not shapes and rows is None:
        raise ValueError("no non-empty blocks and no explicit row count")
    n_rows = rows if rows is not None else shapes[0][0]
    need = max((int(m.nnz) for m in mats if m is not None), default=0)
    budget = align_up(need, align) if need else 0
    r = np.full((len(mats), budget), n_rows, dtype=index_dtype)
    c = np.zeros((len(mats), budget), dtype=index_dtype)
    d = np.zeros((len(mats), budget), dtype=dtype)
    for i, m in enumerate(mats):
        if m is None or m.nnz == 0:
            continue
        r[i], c[i], d[i] = csr_flat_pack(m, pad_to=budget, dtype=dtype,
                                         index_dtype=index_dtype)
    return r, c, d


def csr_flat_spmm(rows: jax.Array, cols: jax.Array,
                  data: Optional[jax.Array], x: jax.Array,
                  n_rows: int) -> jax.Array:
    """Scatter-add SpMM over a flat nonzero list: one extra dummy row
    absorbs padding (row index == n_rows).  ``data=None`` is the
    binary (implicit-ones) mode: padding entries scatter their
    (arbitrary) gathered row into the dummy row, so no values or masks
    are needed at all."""
    gathered = jnp.take(x, cols, axis=0)                     # (nnz, k)
    contrib = gathered if data is None else data[:, None] * gathered
    out = jnp.zeros((n_rows + 1, x.shape[-1]), dtype=jnp.float32)
    out = out.at[rows].add(contrib)
    return out[:n_rows].astype(x.dtype)


def ell_pack_stack_binary(mats: list[sparse.spmatrix],
                          rows: Optional[int] = None,
                          align: int = SLOT_ALIGN,
                          index_dtype=np.int32
                          ) -> tuple[np.ndarray, np.ndarray]:
    """Binary twin of ``ell_pack_stack``: (cols, deg) with cols
    (b, rows, m) and deg (b, rows) int32 — no value array (the caller
    must have verified all values are ones)."""
    shapes = [m.shape for m in mats if m is not None]
    if not shapes and rows is None:
        raise ValueError("no non-empty blocks and no explicit row count")
    rows = rows if rows is not None else shapes[0][0]
    need = 0
    for m in mats:
        if m is None:
            continue
        counts = np.diff(m.tocsr().indptr)
        if counts.size:
            need = max(need, int(counts.max()))
    m_slots = align_up(need, align) if need else 0
    cols = np.zeros((len(mats), rows, m_slots), dtype=index_dtype)
    deg = np.zeros((len(mats), rows), dtype=np.int32)
    for i, m in enumerate(mats):
        if m is None or m.nnz == 0:
            continue
        csr = m.tocsr()
        cols[i], _ = ell_pack(csr, max_nnz=m_slots, with_data=False,
                              index_dtype=index_dtype)
        deg[i] = np.diff(csr.indptr).astype(np.int32)
    return cols, deg


def ell_slot_stats(cols, data=None, deg=None
                   ) -> tuple[np.ndarray, np.ndarray]:
    """Per-entry (nnz, slots) over the leading axis of a stacked ELL
    packing — the raw material of the obs layer's imbalance report
    (obs/imbalance.py).  ``deg`` (binary stacks) counts exactly; with
    only ``data`` padding slots are the zero values; with neither the
    stack is assumed full (indices alone cannot distinguish a real
    column-0 entry from padding).
    """
    cols = np.asarray(cols)
    nb = cols.shape[0]
    slots = np.full(nb, int(np.prod(cols.shape[1:], dtype=np.int64)),
                    dtype=np.int64)
    if deg is not None:
        nnz = np.asarray(deg).reshape(nb, -1).sum(
            axis=1, dtype=np.int64)
    elif data is not None:
        nnz = np.count_nonzero(
            np.asarray(data).reshape(nb, -1), axis=1).astype(np.int64)
    else:
        nnz = slots.copy()
    return nnz, slots


def flat_slot_stats(rows, n_rows: int) -> tuple[np.ndarray, np.ndarray]:
    """Per-entry (nnz, slots) over the leading axis of a flat-COO stack
    (``flat_pack_stack``): padding entries point at the dummy row
    ``n_rows``, so real nonzeros are exactly the in-range rows."""
    rows = np.asarray(rows)
    if rows.ndim == 1:
        rows = rows[None]
    nnz = (rows < n_rows).sum(axis=1, dtype=np.int64)
    slots = np.full(rows.shape[0], rows.shape[1], dtype=np.int64)
    return nnz, slots
