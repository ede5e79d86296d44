"""Multi-level orchestration: iterated SpMM through a whole decomposition.

TPU-native counterpart of the reference's ``ArrowDecompositionMPI``
(reference arrow/arrow_dec_mpi.py).  The reference runs the K arrow
matrices *concurrently on disjoint MPI rank groups*, moving features
forward and partial results backward every iteration through
permutation-routed ``Alltoallv`` exchanges whose counts/displacements are
precomputed into routing tables at init
(arrow_dec_mpi.py:210-281,404-550).

Here the design is deliberately different (SURVEY.md §7 layer 5): all K
levels run **back-to-back on the full mesh**.  With fast ICI, time-sharing
all chips over the levels beats space-sharing them (each level's SpMM
gets the whole machine; no level sits idle waiting for its neighbors),
and the permutation routing collapses to *composed static gather index
arrays* applied to the sharded feature array — XLA lowers a sharded
gather-by-permutation to exactly the all-to-all the routing tables
hand-build in the reference.

Semantics per ``step()`` (matches arrow_dec_mpi.py:283-307):

    X held in level-0 order.                    x_0 = X
    forward:   x_i = x_{i-1}[fwd_i]             (fwd_i = σ_{i-1}^{-1}∘σ_i)
    compute:   c_i = B_i @ x_i                  (slim arrow SpMM)
    backward:  agg_{K-1} = c_{K-1};
               agg_{i-1} = c_{i-1} + agg_i[bwd_i]  (bwd_i = σ_i^{-1}∘σ_{i-1})
    X := agg_0  — the result *in level-0 order* becomes the next
    iteration's features (reference set_features, arrow_dec_mpi.py:438,545).

The result in original row order is ``agg_0[σ_0^{-1}]`` — materialized
only on demand by ``gather_result`` (reference allgather_result analog).

Permutations are padded to the blocked row count with identity tails, and
every level is padded to one shared block count, so all shapes are static
and uniform across the mesh (the reference's dummy-row overflow mapping,
arrow_dec_mpi.py:703-749, becomes plain zero-row padding here).
"""

from __future__ import annotations

import functools
import os
import warnings
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from scipy import sparse

from arrow_matrix_tpu.decomposition.decompose import ArrowLevel
from arrow_matrix_tpu.io.graphio import number_of_blocks, num_rows
from arrow_matrix_tpu.ops.arrow_blocks import (
    ArrowBlocks,
    arrow_blocks_from_csr,
    arrow_blocks_streamed,
    arrow_spmm,
)
from arrow_matrix_tpu.parallel.mesh import (
    fetch_replicated,
    pad_to_multiple,
    put_global,
    shard_arrow_blocks,
)


def gather_budget_for(dense_budget: int) -> int:
    """Byte budget for the ELL gather intermediate, derived from the
    dense-format budget (one rule shared with the profiling tools)."""
    return max(dense_budget // 4, 1 << 27)


def resolve_block_dtype(dtype):
    """Block-storage dtype: numpy dtypes pass through; the strings
    "f32"/"bf16" name the two supported storage modes.  bf16 halves the
    HBM footprint and stream time of the resident blocks — the dominant
    bytes in the bandwidth-bound iteration — while every kernel still
    accumulates in f32 on the MXU (``preferred_element_type`` in
    ops/ell.py and ops/pallas_blocks.py); features stay f32.
    """
    if isinstance(dtype, str):
        import ml_dtypes

        try:
            return {"f32": np.float32, "float32": np.float32,
                    "bf16": ml_dtypes.bfloat16,
                    "bfloat16": ml_dtypes.bfloat16}[dtype]
        except KeyError:
            raise ValueError(f"unknown block dtype {dtype!r} "
                             f"(expected 'f32' or 'bf16')") from None
    return dtype


def resolve_feature_dtype(feature_dtype):
    """Carried-feature storage dtype (None = f32, the gate-exact
    default — normalized so explicit "f32" behaves like None).  bf16
    halves the bytes of every gathered row AND every inter-level
    collective; kernels accumulate each tier's slot sum in f32 with
    full-precision matrix values (ops/ell.py), but the CARRIED value
    rounds to bf16 at tier/level boundaries — ~1e-3 rel err/step,
    outside the f32 gate.

    Contract: executors consult ``self.feature_dtype`` only in
    ``set_features`` (operators are dtype-independent), so retargeting
    the attribute between calls measures both carriages against one
    build — bench.py's k128 rerun and tools/gather_probe.py rely on
    this.

    "int8" (graft-classes, fold path only) quantizes the carriage to a
    symmetric per-feature-row int8 ``(q, scale)`` pair — 4× fewer
    carriage bytes; SpMM column-separability makes the per-row scale
    exact (see ``_finalize_folded``), so the only error is the
    per-step requantization round."""
    if feature_dtype is None:
        return None
    if feature_dtype == "int8" or (not isinstance(feature_dtype, str)
                                   and np.dtype(feature_dtype)
                                   == np.dtype(np.int8)):
        return np.int8
    resolved = resolve_block_dtype(feature_dtype)
    return None if resolved == np.float32 else resolved


def resolve_levels_binary(levels, binary) -> bool:
    """Decomposition-wide binary decision (see MultiLevelArrow): "auto"
    resolves True iff every level is implicit-ones / all-ones; an
    explicit bool is validated per level (forcing binary on non-unit
    values raises)."""
    from arrow_matrix_tpu.ops.arrow_blocks import resolve_blocks_binary

    if binary is False:
        return False
    return all(resolve_blocks_binary(lvl.matrix, "ell", binary)
               for lvl in levels)


def pad_permutation(perm: np.ndarray, total: int) -> np.ndarray:
    """Extend a permutation of [0, n) to [0, total) with an identity tail
    (padding rows are zero and permute among themselves)."""
    n = perm.size
    if n > total:
        raise ValueError(f"permutation length {n} exceeds padded rows {total}")
    return np.concatenate([perm.astype(np.int64),
                           np.arange(n, total, dtype=np.int64)])


def compose_routing(perms: Sequence[np.ndarray], total: int
                    ) -> tuple[np.ndarray, np.ndarray]:
    """Static routing index arrays replacing the reference's Alltoallv
    tables (arrow_dec_mpi.py:210-281).

    Returns (fwd, bwd), each (K-1, total) int32:
      fwd[i-1] maps level-(i-1)-ordered rows to level-i order:
          x_i = x_{i-1}[fwd[i-1]],  fwd[i-1] = inv(σ_{i-1})[σ_i]
      bwd[i-1] maps level-i-ordered rows to level-(i-1) order:
          agg_{i-1} += agg_i[bwd[i-1]],  bwd[i-1] = inv(σ_i)[σ_{i-1}]
    """
    padded = [pad_permutation(np.asarray(p), total) for p in perms]
    fwd, bwd = [], []
    for i in range(1, len(padded)):
        inv_prev = np.argsort(padded[i - 1])
        inv_cur = np.argsort(padded[i])
        fwd.append(inv_prev[padded[i]])
        bwd.append(inv_cur[padded[i - 1]])
    if not fwd:
        return (np.zeros((0, total), np.int32),) * 2
    return (np.stack(fwd).astype(np.int32), np.stack(bwd).astype(np.int32))


class MultiLevelArrow:
    """Device-resident multi-level arrow decomposition + jitted step.

    Construction tiles every level's CSR into ArrowBlocks padded to one
    shared flat row count (divisible by the mesh block axis), builds the
    composed routing tables, and places everything on the mesh.  This
    replaces the reference's entire distributed-load machinery
    (arrow_dec_mpi.py:629-887: root-reads-and-ships-blocks) with sharded
    `device_put`.

    A last level whose *achieved* width exceeds the requested width (the
    decomposition keeps all remaining edges there) is tiled at its own
    block width — the achieved width rounded up to a multiple of the base
    width — in banded mode, which provably covers every |r-c| <= W entry.
    The reference instead loads every level at the fixed width and
    silently drops out-of-pattern nonzeros (SURVEY.md §7 known bugs); we
    stay exact.

    ``step(x)`` runs one full iteration; iterate by feeding the result
    back (the reference's benchmark loop, arrow_bench.py:111-134).
    Features are carried as flat (total_rows, k) arrays sharded on the
    row axis; each level reshapes to its own (nb_i, w_i, k) blocking.
    """

    def __init__(self, levels: List[ArrowLevel], width: int,
                 mesh: Optional[Mesh] = None, axis: str = "blocks",
                 banded: bool = False, dtype=np.float32,
                 chunk="auto", fmt: str = "auto",
                 dense_budget: Optional[int] = None, kernel: str = "xla",
                 routing: str = "gather", head_fmt: str = "auto",
                 binary="auto", feature_dtype=None,
                 layout: str = "slim", arm_axis: str = "arm",
                 fold_growth: float = 1.2,
                 fold_align: Optional[int] = None,
                 overlap_slabs: int = 1,
                 plan=None, plan_k: Optional[int] = None,
                 kernel_opts: Optional[dict] = None,
                 exchange_scratch_budget: int = 0,
                 exchange_k: Optional[int] = None):
        """``routing`` selects the inter-level exchange lowering:
        "gather" leaves the permutation gathers to GSPMD (which may
        all-gather the whole feature array per exchange), "a2a" compiles
        them into explicit per-device send/recv tables over one
        fixed-shape all_to_all (parallel/routing.py — O(moved rows)
        volume, the reference's Alltoallv tables,
        arrow_dec_mpi.py:210-281).  "a2a" requires a mesh and carries
        the features sharded on rows only."""
        if not levels:
            raise ValueError("empty decomposition")
        # graft-tune consumption: a resolved TunePlan REPLACES the
        # per-knob arguments (the plan is one configuration object —
        # hand-set knobs compose with plan=None).  ``plan="auto"``
        # hashes the structure and looks the cache up; a miss or
        # version skew warns TunePlanMiss and proceeds on the defaults
        # given here — loudly, never silently.
        self.tune_plan = None
        self.kernel_opts = dict(kernel_opts) if kernel_opts else {}
        if plan is not None:
            if mesh is not None:
                warnings.warn(
                    "tune plans target the single-chip fold path; "
                    "ignoring plan= on a mesh "
                    "(SellSlim/SellMultiLevel consume plans for the "
                    "mesh executors)", UserWarning, stacklevel=2)
            else:
                from arrow_matrix_tpu.tune.plan import resolve_plan

                resolved = resolve_plan(
                    plan, levels=levels, width=width, dtype=dtype,
                    growth=fold_growth, slot_align=fold_align,
                    binary=binary, plan_k=plan_k)
                if resolved is not None:
                    self.tune_plan = resolved
                    bk = resolved.build_kwargs()
                    fmt = bk["fmt"]
                    kernel = bk["kernel"]
                    chunk = bk["chunk"]
                    fold_growth = bk["fold_growth"]
                    fold_align = bk["fold_align"]
                    feature_dtype = bk["feature_dtype"]
                    # Explicit kernel_opts beat the plan's (a caller
                    # overriding one fused-kernel knob keeps the rest).
                    self.kernel_opts = {**resolved.kernel_opts(),
                                        **self.kernel_opts}
        dtype = resolve_block_dtype(dtype)
        # Carried-feature storage dtype — the k=128 amortization
        # lever, where the gather turns bandwidth-bound
        # (PERFORMANCE.md cost model).
        self.feature_dtype = resolve_feature_dtype(feature_dtype)
        if self.feature_dtype is not None and fmt != "fold":
            raise ValueError(
                "feature_dtype is implemented for fmt='fold' (the "
                "single-chip headline path); other formats carry f32")
        if routing not in ("gather", "a2a"):
            raise ValueError(f"unknown routing {routing!r}")
        if head_fmt == "gell" and mesh is not None:
            raise ValueError(
                "head_fmt='gell' is the single-chip head layout (its "
                "gather reads the whole feature array); use 'flat', "
                "'ell' or 'auto' on a mesh")
        if fmt == "fold" and mesh is not None:
            raise ValueError(
                "fmt='fold' is a single-chip whole-decomposition kernel "
                "(the arrow block structure exists to shape "
                "communication; within one chip one general SELL SpMM "
                "replaces it, the way the reference's per-rank "
                "cuSPARSE CSRMM does — sp2cp.py:6-16); use "
                "'auto'/'dense'/'ell' on a mesh")
        if routing == "a2a" and mesh is None:
            raise ValueError("routing='a2a' requires a mesh")
        # graft-reshard consumer (b): a positive budget splits every
        # a2a exchange into bounded-scratch stages
        # (routing.split_route_stages) instead of one full-width
        # all_to_all.  Stage sizing needs the feature width at build
        # time — ``exchange_k`` (or the tune plan's ``plan_k``).
        self.exchange_scratch_budget = int(exchange_scratch_budget)
        self._exchange_k = exchange_k if exchange_k is not None else plan_k
        if self.exchange_scratch_budget > 0:
            if routing != "a2a":
                raise ValueError(
                    "exchange_scratch_budget bounds the explicit a2a "
                    "exchange; routing='gather' leaves the exchange to "
                    "GSPMD where no budget can be enforced")
            if self._exchange_k is None:
                raise ValueError(
                    "exchange_scratch_budget needs the feature width to "
                    "size stages — pass exchange_k (or plan_k)")
        # Wide layout: per-level SpMM on a (2, t) mesh with disjoint
        # row-arm / column-arm device groups (the reference composes
        # the wide ArrowMPI into ArrowDecompositionMPI the same way,
        # arrow_dec_mpi.py:134,165).  Orchestration (routing gathers,
        # backward aggregation) is unchanged: features stay sharded on
        # the block axis, replicated over the arm axis.
        if layout not in ("slim", "wide"):
            raise ValueError(f"unknown layout {layout!r} "
                             f"(expected 'slim' or 'wide')")
        if layout == "wide":
            if mesh is None:
                raise ValueError(
                    "layout='wide' needs a (arm=2, blocks) mesh — the "
                    "reference's 2t-1-rank row/column split "
                    "(arrow_mpi.py:31-69); on one chip use 'slim'")
            if arm_axis not in mesh.axis_names \
                    or mesh.shape[arm_axis] != 2:
                raise ValueError(
                    f"layout='wide' needs mesh axis {arm_axis!r} of "
                    f"size 2, got axes {dict(mesh.shape)}")
            if kernel == "pallas":
                raise ValueError(
                    "layout='wide' runs the XLA shard_map step; the "
                    "fused pallas kernels cover the slim layout")
            if routing == "a2a":
                raise ValueError(
                    "layout='wide' composes with routing='gather' (the "
                    "a2a tables are built for the 1-axis slim feature "
                    "sharding)")
        self.layout = layout
        self.arm_axis = arm_axis
        if dense_budget is None:
            # Budget from the actual target chip's free memory, not a
            # constant (VERDICT r1: 4GiB misformats on both v5e and v5p).
            # Blocks shard over the mesh, so the *global* footprints
            # compared below get one chip's budget per device.
            from arrow_matrix_tpu.utils.platform import device_memory_budget

            dev = mesh.devices.flat[0] if mesh is not None else None
            dense_budget = device_memory_budget(dev)
            if mesh is not None:
                dense_budget *= mesh.shape[axis]
        self.dense_budget = dense_budget
        if kernel not in ("xla", "pallas", "pallas_sell"):
            raise ValueError(f"unknown kernel {kernel!r}")
        if kernel == "pallas_sell" and fmt != "fold":
            raise ValueError(
                "kernel='pallas_sell' is the fused fold kernel "
                "(ops/pallas_sell.py); it requires fmt='fold'")
        self.kernel = kernel
        if overlap_slabs < 1:
            raise ValueError(f"overlap_slabs must be >= 1, got "
                             f"{overlap_slabs}")
        # The overlap schedule splits the features so a slab's
        # exchange can fly while another slab computes: on one chip the
        # fold has no exchange to hide, and every slab would gather
        # every slot again.
        if overlap_slabs > 1 and fmt == "fold":
            raise ValueError(
                f"overlap_slabs={overlap_slabs} hides a mesh's "
                "exchanges; the one-chip fold has none — use the mesh "
                "executors (SellMultiLevel/SellSlim overlap_slabs, or "
                "MultiLevelArrow on a mesh)")
        self.overlap_slabs = int(overlap_slabs)
        self.width = width
        self.mesh = mesh
        self.axis = axis
        self.banded = banded
        self.chunk = chunk
        self.n = num_rows(levels[0].matrix)

        n_dev = mesh.shape[axis] if mesh is not None else 1

        # Per-level block widths.  A level whose achieved width exceeds
        # the base width (always possible for the last level, which keeps
        # *all* remaining edges under a band bound; also the decomposer's
        # keep-everything fallback) is tiled at its achieved width rounded
        # up to a multiple of the base width, in banded mode — banded
        # tiling at block width W covers every |r-c| <= W entry.  The
        # last level's structure is a band even in block-diagonal mode,
        # so it is always banded.
        widths, bandeds = [], []
        for i, lvl in enumerate(levels):
            is_last = i == len(levels) - 1
            if lvl.arrow_width > width or is_last:
                widths.append(-(-lvl.arrow_width // width) * width)
                bandeds.append(True)
            else:
                widths.append(width)
                bandeds.append(banded)
        self.widths = widths

        # One shared flat row count, a multiple of every level's block
        # width times the device count (widths[-1] is the only non-base
        # width and is itself a multiple of the base width).
        unit = n_dev * max(widths)
        max_rows = max(number_of_blocks(lvl.matrix, w) * w
                       for lvl, w in zip(levels, widths))
        self.total_rows = pad_to_multiple(max_rows, unit)

        # Binary (implicit-ones) mode is decided ONCE for the whole
        # decomposition: a per-level auto decision could mix binary and
        # weighted levels, which the stacked space-shared layout (and
        # any cross-level pytree stacking) cannot represent.  "auto"
        # means binary iff EVERY level is all-ones.
        self.binary = resolve_levels_binary(levels, binary)

        gather_budget = gather_budget_for(dense_budget)
        self.folded = fmt == "fold"
        # The carried-layout capability flag the models key on
        # (SGCCarried/GCNCarried vs the flat SGCModel/GCNModel).
        self.carries_feature_major = self.folded
        if self.folded:
            self._init_folded(levels, chunk, gather_budget, dtype,
                              growth=fold_growth, slot_align=fold_align)
            return

        # Per-level block format.  "auto" densifies levels as long as the
        # *cumulative* dense footprint (total_rows · w · n_stacks ·
        # itemsize per level — an arrow matrix has 3 structural block
        # stacks, 5 banded) stays inside the budget: dense blocks run as
        # batched MXU matmuls, the ELL gather path is the fallback for
        # widths too large to densify.
        itemsize = np.dtype(dtype).itemsize
        budget_left = dense_budget
        self.fmts = []
        for w, bd in zip(widths, bandeds):
            if fmt == "auto":
                stacks = 5 if bd else 3
                dense_bytes = self.total_rows * w * stacks * itemsize
                if dense_bytes <= budget_left:
                    budget_left -= dense_bytes
                    self.fmts.append("dense")
                else:
                    self.fmts.append("ell")
            else:
                self.fmts.append(fmt)

        if kernel == "pallas" and "dense" not in self.fmts:
            raise ValueError(
                "kernel='pallas' but no level resolved to the dense block "
                "format (the pallas kernels cover dense only; raise "
                "dense_budget or pass fmt='dense')")

        # Level matrices pass through as-is: an in-memory CSR or a
        # memmapped CsrLike triplet.  Triplet levels on a mesh take the
        # streaming builder — per-device-shard packing bounds peak host
        # RSS to O(level / n_devices) so >RAM artifacts ingest without
        # ever materializing a level (the reference's
        # root-reads-and-ships loader role, arrow_dec_mpi.py:629-887).
        def resolve_head_fmt(lvl, w, f) -> str:
            """Platform-aware "auto": on a single TPU chip an ELL
            level's head goes gell when compact — the flat head's
            scatter-add serializes on TPU, the gell gather streams —
            falling back to the flat/ell size rule when one mega-degree
            head row would blow the gell slot budget."""
            if head_fmt != "auto" or mesh is not None or f != "ell":
                return head_fmt
            if jax.default_backend() != "tpu":
                return head_fmt
            indptr = (lvl.matrix.indptr
                      if isinstance(lvl.matrix, sparse.csr_matrix)
                      else lvl.matrix[2])
            w_eff = min(w, indptr.shape[0] - 1)
            counts = np.diff(np.asarray(indptr[:w_eff + 1]))
            need = int(counts.max()) if counts.size else 0
            gell_bytes = w * need * (4 + np.dtype(dtype).itemsize)
            return "gell" if gell_bytes <= dense_budget // 8 else "auto"

        def build(lvl, w, bd, f):
            hf = resolve_head_fmt(lvl, w, f)
            if mesh is not None and not isinstance(lvl.matrix,
                                                   sparse.csr_matrix):
                return arrow_blocks_streamed(
                    lvl.matrix, w, mesh, axis,
                    pad_blocks_to=self.total_rows // w,
                    banded=bd, dtype=dtype, fmt=f, head_fmt=hf,
                    binary=self.binary)
            return arrow_blocks_from_csr(lvl.matrix, w,
                                         pad_blocks_to=self.total_rows // w,
                                         banded=bd, dtype=dtype, fmt=f,
                                         head_fmt=hf, binary=self.binary)

        self.blocks: List[ArrowBlocks] = [
            build(lvl, w, bd, f)
            for lvl, w, bd, f in zip(levels, widths, bandeds, self.fmts)
        ]
        fwd, bwd = compose_routing([lvl.permutation for lvl in levels],
                                   self.total_rows)
        self.perm0 = pad_permutation(np.asarray(levels[0].permutation),
                                     self.total_rows)
        self.inv_perm0 = np.argsort(self.perm0)

        # Paper cost model of the inter-level routing in row-units
        # (k=1, itemsize=1): only rows whose adjacent-level positions
        # land on different devices move (the reference Alltoallv
        # payload).  Single chip: no routing exchange at all.
        if mesh is not None:
            from arrow_matrix_tpu.utils import commstats

            padded = [pad_permutation(np.asarray(lvl.permutation),
                                      self.total_rows)
                      for lvl in levels]
            self._ideal_route_units = commstats.ideal_routing_bytes(
                padded, mesh.shape[axis], 1, itemsize=1)
        else:
            self._ideal_route_units = 0

        self.routing = routing
        if mesh is not None:
            self.blocks = [shard_arrow_blocks(b, mesh, axis)
                           for b in self.blocks]
            if routing == "a2a":
                from arrow_matrix_tpu.parallel.routing import (
                    build_route,
                    shard_route,
                    split_route_stages,
                )

                n_dev = mesh.shape[axis]

                def compile_route(t):
                    r = build_route(t, n_dev)
                    if self.exchange_scratch_budget > 0:
                        r = split_route_stages(
                            r, int(self._exchange_k),
                            self.exchange_scratch_budget,
                            itemsize=np.dtype(
                                self.feature_dtype
                                or np.float32).itemsize)
                    return shard_route(r, mesh, axis)

                self.fwd = [compile_route(t) for t in fwd]
                self.bwd = [compile_route(t) for t in bwd]
            else:
                # Routing tables replicated (they index global rows).
                repl = NamedSharding(mesh, P())
                self.fwd = put_global(np.asarray(fwd), repl)
                self.bwd = put_global(np.asarray(bwd), repl)
        else:
            self.fwd = jnp.asarray(fwd)
            self.bwd = jnp.asarray(bwd)

        # chunk="auto" sizes the ELL gather intermediate from the same
        # hardware-derived budget as the format choice (resolved per
        # level at trace time — shapes are static under jit).
        # Blocks are explicit jit arguments, not closure captures: captured
        # arrays are inlined into the HLO as literal constants, which
        # bloats the program (and breaks remote-compile size limits).
        self._step = jax.jit(functools.partial(
            multi_level_spmm, widths=tuple(widths), chunk=chunk,
            kernel=kernel, gather_budget=gather_budget,
            mesh=mesh, axis=axis, layout=layout, arm_axis=arm_axis,
            overlap_slabs=self.overlap_slabs))

        def scan_steps(x, fwd, bwd, blocks, n):
            def body(xc, _):
                xc = multi_level_spmm(xc, fwd, bwd, blocks,
                                      widths=tuple(widths), chunk=chunk,
                                      kernel=kernel,
                                      gather_budget=gather_budget,
                                      mesh=mesh, axis=axis,
                                      layout=layout, arm_axis=arm_axis,
                                      overlap_slabs=self.overlap_slabs)
                return xc, None

            out, _ = jax.lax.scan(body, x, None, length=n)
            return out

        self._scan_steps = jax.jit(scan_steps, static_argnames=("n",))
        self._scan_steps_donated = jax.jit(scan_steps,
                                           static_argnames=("n",),
                                           donate_argnums=(0,))

    # -- folded single-chip execution --------------------------------------

    def _init_folded(self, levels, chunk, gather_budget: int, dtype,
                     growth: float = 1.2,
                     slot_align: Optional[int] = None) -> None:
        """Compose the whole decomposition into ONE operator.

        On a single chip the inter-level permutation exchanges buy
        nothing: they are 2(K-1) full feature-array gathers per
        iteration, each paying the XLA gather rate.  Exact identity:
        ``A = sum_i P_i^T B_i P_i`` (the decomposition partitions the
        edge set — reference tests/test_arrowdecomposition.py:93-99), so
        the host reconstructs A conjugated into level-0 order and packs
        it as one SELL operator; the step becomes a single general SpMM with
        zero routing (the honest single-chip execution — the reference
        at one rank likewise runs its whole share as one CSRMM).
        Binary (all-ones) level data folds to a binary operator: levels
        are edge-disjoint, so no duplicate positions sum.

        Host-memory note: folding materializes the nnz triplets once
        (O(nnz) host RAM); the streamed >RAM ingestion path keeps the
        per-level formats on a mesh instead.
        """
        from arrow_matrix_tpu.obs.tracer import get_tracer
        from arrow_matrix_tpu.ops.sell import sell_from_csr

        with get_tracer().span("fold.compose"):
            folded = self._compose_folded(levels)

        # SELL packing in degree-sorted coordinates; the sort permutation
        # is composed into the carried ordering (set_features/
        # gather_result), so it is free at runtime.  Tiers hold exact
        # degrees unless the caller aligns them.
        sell, order = sell_from_csr(folded, pad_rows_to=self.total_rows,
                                    dtype=dtype, binary=self.binary,
                                    growth=growth,
                                    slot_align=slot_align or 1)
        self.perm0 = self.perm0[order]
        self.inv_perm0 = np.argsort(self.perm0)
        self._finalize_folded(sell, chunk, gather_budget,
                              kernel=self.kernel,
                              kernel_opts=self.kernel_opts)

    def _compose_folded(self, levels) -> sparse.csr_matrix:
        """Every level's triplets conjugated into level-0 order as one
        CSR (sets ``perm0`` / ``inv_perm0`` to level 0's)."""
        total = self.total_rows
        perms = [pad_permutation(np.asarray(lvl.permutation), total)
                 for lvl in levels]
        self.perm0 = perms[0]
        self.inv_perm0 = np.argsort(self.perm0)

        rows_l, cols_l, data_l = [], [], []
        implicit_ones = True
        for lvl, p in zip(levels, perms):
            mp = self.inv_perm0[p]          # level-i index -> level-0 index
            if isinstance(lvl.matrix, sparse.csr_matrix):
                coo = lvl.matrix.tocoo()
                r, c, d = coo.row, coo.col, coo.data
            else:
                d, indices, indptr = lvl.matrix
                indptr = np.asarray(indptr, dtype=np.int64)
                nnz = int(indptr[-1])
                r = np.repeat(np.arange(indptr.size - 1),
                              np.diff(indptr)).astype(np.int64)
                c = np.asarray(indices[:nnz])
                if d is not None:
                    d = np.asarray(d[:nnz])
            rows_l.append(mp[r])
            cols_l.append(mp[c])
            if d is None:
                data_l.append(np.ones(len(rows_l[-1]), dtype=np.float32))
            else:
                implicit_ones = False
                data_l.append(np.asarray(d, dtype=np.float32))

        folded = sparse.csr_matrix(
            (np.concatenate(data_l),
             (np.concatenate(rows_l), np.concatenate(cols_l))),
            shape=(total, total))
        folded.sum_duplicates()
        folded.sort_indices()
        if implicit_ones and not np.all(folded.data == 1.0):
            raise AssertionError(
                "edge-disjoint levels folded to duplicate positions")
        return folded

    def _finalize_folded(self, sell, chunk, gather_budget: int, *,
                         kernel: str, kernel_opts: Optional[dict]
                         ) -> None:
        """Install a packed SELL operator as the fold execution state
        (shared by the levels build and ``load_folded``).  One step is
        one SpMM of the whole carriage: the XLA ``sell_spmm_t`` under
        the device-derived gather budget, or the fused ``pallas_sell``
        kernel; an int8 carriage wraps it in the requantisation."""
        from arrow_matrix_tpu.ops.sell import sell_spmm_t

        self.blocks = [sell]
        self.fmts = ["fold"]
        self.routing = "none"
        self.fwd = self.bwd = ()
        self._ideal_route_units = 0  # single-chip fold: zero routing
        self.kernel = kernel
        # Tuned fused-kernel call knobs (graft-tune): row_block / wave
        # / smem_cols_budget / ring, captured at build time — no env
        # reads inside the jitted step (lint R9).
        self.kernel_opts = kopts = dict(kernel_opts or {})

        int8_carry = (self.feature_dtype is not None
                      and np.dtype(self.feature_dtype)
                      == np.dtype(np.int8))

        def fold_step(xt, fwd, bwd, blocks):
            if xt.dtype == jnp.int8:
                if kernel == "pallas_sell":
                    # Fused (q, scale) carriage: the quantized table
                    # streams through the kernel AS int8 granule lines
                    # (f32 accumulate, KC4); the per-feature scale is
                    # applied by fold_step_q outside — 4x fewer gather
                    # bytes than widening first.
                    from arrow_matrix_tpu.ops.pallas_sell import (
                        sell_spmm_t_pallas,
                    )

                    opts = {kk: vv for kk, vv in kopts.items()
                            if kk != "feature_dtype"}
                    return sell_spmm_t_pallas(blocks[0], xt,
                                              feature_dtype="int8",
                                              **opts)
                # f32 transient of the step's input: the carriage
                # itself stays int8 in HBM between steps.
                xt = xt.astype(jnp.float32)
            if kernel == "pallas_sell":
                # Fused gather->FMA kernel: no materialized gather
                # intermediate, so no chunk/gather_budget tiling.
                from arrow_matrix_tpu.ops.pallas_sell import (
                    sell_spmm_t_pallas,
                )

                # The carriage dtype is declared explicitly (KC4: the
                # kernel accumulates f32 regardless), and follows the
                # features as delivered — set_features retargeting
                # keeps working because xt.dtype is a trace-time
                # static, not a build-time capture.  int8 was widened
                # above, so it always lands on the f32 carriage.
                fd = kopts.get("feature_dtype") or (
                    "bf16" if xt.dtype == jnp.bfloat16 else "f32")
                opts = {kk: vv for kk, vv in kopts.items()
                        if kk != "feature_dtype"}
                return sell_spmm_t_pallas(blocks[0], xt,
                                          feature_dtype=fd, **opts)
            if chunk == "auto":
                return sell_spmm_t(blocks[0], xt,
                                   gather_budget=gather_budget)
            return sell_spmm_t(blocks[0], xt, chunk=chunk)

        def fold_step_q(carry, fwd, bwd, blocks):
            # int8 carriage (graft-classes): the carry is a symmetric
            # per-feature-row quantized pair — q int8 (k, total), scale
            # f32 (k, 1).  Feature-major layout means carriage row f is
            # one feature column of X, and SpMM is column-separable, so
            # fold(q * scale) == fold(q) * scale EXACTLY: the scale
            # rides outside the (f32-accumulated) operator and the only
            # approximation is the requantization round below.
            q, scale = carry
            z = fold_step(q, fwd, bwd, blocks) * scale
            amax = jnp.max(jnp.abs(z), axis=1, keepdims=True)
            safe = jnp.where(amax > 0.0, amax, 1.0)
            q2 = jnp.clip(jnp.round(z * (127.0 / safe)),
                          -127.0, 127.0).astype(jnp.int8)
            s2 = jnp.where(amax > 0.0, amax / 127.0, 0.0)
            return q2, s2

        step_fn = fold_step_q if int8_carry else fold_step
        self._step = jax.jit(step_fn)

        def fold_scan(xt, fwd, bwd, blocks, n):
            def body(xc, _):
                return step_fn(xc, fwd, bwd, blocks), None

            out, _ = jax.lax.scan(body, xt, None, length=n)
            return out

        self._scan_steps = jax.jit(fold_scan, static_argnames=("n",))
        self._scan_steps_donated = jax.jit(fold_scan,
                                           static_argnames=("n",),
                                           donate_argnums=(0,))

    def export_folded(self, out_dir: str) -> None:
        """Write the PACKED fold operator to ``out_dir`` (per-tier SELL
        arrays + carried permutation + meta.json) so a later process —
        in particular the on-chip bench stage at the 10^8-row scale —
        can ``load_folded`` and step without redoing the decompose and
        fold (hours of host work at 2^27).  The offline/online split of
        the decomposition I/O scheme, applied at the operator level."""
        import json

        if not self.folded:
            raise ValueError("export_folded requires fmt='fold'")
        os.makedirs(out_dir, exist_ok=True)
        sell = self.blocks[0]
        np.save(os.path.join(out_dir, "perm0.npy"), self.perm0)
        for t, cols in enumerate(sell.cols):
            np.save(os.path.join(out_dir, f"cols_{t}.npy"),
                    np.asarray(cols))
            if sell.binary:
                np.save(os.path.join(out_dir, f"deg_{t}.npy"),
                        np.asarray(sell.deg[t]))
            else:
                np.save(os.path.join(out_dir, f"data_{t}.npy"),
                        np.asarray(sell.data[t]))
        meta = {"n": int(self.n), "total_rows": int(self.total_rows),
                "binary": bool(sell.binary),
                "n_tiers": len(sell.cols),
                "row_starts": [int(s) for s in sell.row_starts],
                "n_slots": int(sell.n_slots),
                "feature_dtype": (np.dtype(self.feature_dtype).name
                                  if self.feature_dtype is not None
                                  else None)}
        with open(os.path.join(out_dir, "meta.json"), "w") as f:
            json.dump(meta, f, indent=1)

    @classmethod
    def load_folded(cls, in_dir: str, feature_dtype="keep",
                    chunk="auto", gather_budget: int = 1 << 30,
                    device_put: bool = True, kernel: str = "xla"
                    ) -> "MultiLevelArrow":
        """Rebuild a fold executor from an ``export_folded`` directory
        without the source decomposition.  ``feature_dtype="keep"``
        uses the exported carriage dtype; ``device_put=False`` keeps
        the tier arrays as host memmaps (budget accounting / tests)."""
        import json

        from arrow_matrix_tpu.ops.sell import SellMatrix, upload_sell

        with open(os.path.join(in_dir, "meta.json")) as f:
            meta = json.load(f)
        self = cls.__new__(cls)
        self.n = meta["n"]
        self.total_rows = meta["total_rows"]
        self.binary = meta["binary"]
        self.mesh = None
        self.axis = "blocks"
        self.folded = True
        self.carries_feature_major = True
        self.overlap_slabs = 1
        if feature_dtype == "keep":
            feature_dtype = meta["feature_dtype"]
        self.feature_dtype = resolve_feature_dtype(feature_dtype)
        self.perm0 = np.load(os.path.join(in_dir, "perm0.npy"))
        self.inv_perm0 = np.argsort(self.perm0)
        cols_t, deg_t, data_t = [], [], []
        for t in range(meta["n_tiers"]):
            cols_t.append(np.asarray(np.load(
                os.path.join(in_dir, f"cols_{t}.npy"), mmap_mode="r")))
            if meta["binary"]:
                deg_t.append(np.load(os.path.join(in_dir, f"deg_{t}.npy")))
            else:
                data_t.append(np.asarray(np.load(
                    os.path.join(in_dir, f"data_{t}.npy"),
                    mmap_mode="r")))
        sell = SellMatrix(
            cols=tuple(cols_t),
            data=None if meta["binary"] else tuple(data_t),
            deg=tuple(deg_t) if meta["binary"] else None,
            n_rows=meta["total_rows"],
            row_starts=tuple(meta["row_starts"]))
        if device_put:
            sell = upload_sell(sell)
        self._finalize_folded(sell, chunk, gather_budget, kernel=kernel,
                              kernel_opts=None)
        return self

    # -- feature placement -------------------------------------------------

    def _rows_sharding(self):
        return NamedSharding(self.mesh, P(self.axis))

    def place_features(self, x_level0: np.ndarray) -> jax.Array:
        """Host (total_rows, k) features *already in level-0 order* ->
        flat sharded device array."""
        if self.mesh is None:
            return jnp.asarray(x_level0)
        return put_global(x_level0, self._rows_sharding())

    def set_features(self, x_original: np.ndarray) -> jax.Array:
        """Host (n, k) features in *original* row order -> device array in
        level-0 order (reference set_features on matrix 0,
        arrow_bench.py:114-116).  Folded mode returns (and ``step``/
        ``run`` carry) the feature-major (k, total_rows) layout — the
        padding-free device layout; ``gather_result`` undoes it."""
        x_original = np.asarray(x_original)
        n, k = x_original.shape
        if n != self.n:
            raise ValueError(f"expected {self.n} rows, got {n}")
        # Level-0 order straight from the input: perm0's identity tail
        # (indices >= n) names padding rows, zeroed after the gather.
        feat = x_original[np.minimum(self.perm0, n - 1)]
        feat[self.perm0 >= n] = 0
        if self.folded:
            if self.feature_dtype is not None \
                    and np.dtype(self.feature_dtype) == np.dtype(np.int8):
                # graft-classes int8 carriage: symmetric per-feature-row
                # quantization into the (q, scale) carry pair the int8
                # fold step requantizes each iteration.
                xt = np.ascontiguousarray(feat.T).astype(np.float32)
                amax = np.max(np.abs(xt), axis=1, keepdims=True)
                safe = np.where(amax > 0.0, amax, 1.0)
                q = np.clip(np.rint(xt * (127.0 / safe)),
                            -127.0, 127.0).astype(np.int8)
                scale = np.where(amax > 0.0, amax / 127.0,
                                 0.0).astype(np.float32)
                return (jnp.asarray(q), jnp.asarray(scale))
            if self.feature_dtype is not None:
                feat = feat.astype(self.feature_dtype)  # before the
                # upload: half the bytes at 2^24-row scale
            # The transpose runs on the device: a host transpose of a
            # 2^22 x 128 block costs seconds per call.
            return jnp.asarray(feat).T
        return self.place_features(feat)

    def real_row_mask(self, dtype=np.float32) -> jax.Array:
        """(total_rows, 1) device mask: 1 for rows backed by an original
        matrix row, 0 for padding.  Row r of the level-0 layout is real
        iff its original index ``perm0[r] < n`` (perm0 pads with an
        identity tail).  Use this to keep padding rows out of losses,
        teleport mass, and other per-row reductions."""
        if self.folded:
            raise ValueError(
                "real_row_mask is undefined for fmt='fold' (feature-"
                "major step/run-only execution; the propagation models "
                "that consume the mask reject fold up front)")
        return self.place_features(
            (self.perm0 < self.n).astype(dtype)[:, None])

    def gather_result(self, c: jax.Array) -> np.ndarray:
        """Device result (level-0 order, flat) -> host (n, k) array in
        original row order (reference allgather_result analog)."""
        if self.folded:
            if isinstance(c, tuple):
                # int8 (q, scale) carry: dequantize on host.
                q, scale = c
                arr = (np.asarray(q, dtype=np.float32)
                       * np.asarray(scale, dtype=np.float32))
                return arr.T[self.inv_perm0][:self.n]
            # bf16-carried results come back as f32 numpy (downstream
            # host math — goldens, norms — has no bf16 arithmetic).
            # Transposed on the device, so the host gathers whole rows.
            return fetch_replicated(jnp.asarray(c, jnp.float32).T)[
                self.inv_perm0[:self.n]]
        return fetch_replicated(c)[self.inv_perm0][:self.n]

    # -- iteration ---------------------------------------------------------

    @property
    def step_fn(self):
        """The jitted step callable, public half of the pair
        ``step(x) == step_fn(x, *step_operands())`` — for callers
        (models) that trace the step inside their own jit."""
        return self._step

    def step_operands(self):
        """The device operands of one step, for callers that trace the
        step inside their own jit (models): ``step(x) ==
        step_fn(x, *step_operands())`` — threading these as jit
        ARGUMENTS keeps them out of the trace as baked constants."""
        return (self.fwd, self.bwd, self.blocks)

    def carried_mask(self) -> jax.Array:
        """(1, total_rows) validity mask of the folded feature-major
        carriage: 1 where a position holds a real original row.  The
        fold counterpart of ``real_row_mask`` — fold pads carry zeros
        through the operator, but loss denominators and whole-state
        reductions must still count only real rows."""
        if not self.folded:
            raise ValueError(
                "carried_mask is defined for fmt='fold' (feature-major "
                "carriage); the flat layouts use real_row_mask")
        return jnp.asarray(
            (self.perm0 < self.n).astype(np.float32)[None, :])

    def step(self, x: jax.Array) -> jax.Array:
        """One iteration ``X := A @ X`` through all levels; input and
        output are flat (total_rows, k) arrays in level-0 order."""
        from arrow_matrix_tpu.faults import on_step as _fault_hook

        if isinstance(x, tuple):
            # int8 (q, scale) carry: the fault hook reads shapes and
            # poisons floats, so it rides on the f32 scale component.
            q, scale = x
            x = (q, _fault_hook("multi_level.step", scale))
        else:
            x = _fault_hook("multi_level.step", x)
        return self._step(x, self.fwd, self.bwd, self.blocks)

    def ideal_comm_bytes(self, k: int, itemsize: int = 4) -> int:
        """Paper cost model for one step at feature width ``k``:
        inter-level permutation routing counts only rows that change
        device (zero on a single chip or under fmt='fold') — the bound
        obs/comm judges the compiled collective bytes against."""
        return self._ideal_route_units * k * itemsize

    def reduce_comm_bytes(self, k: int, itemsize: int = 4) -> int:
        """2.5D final-reduction bytes: always 0 here — this class has
        no replica axis (see SellSlim/SellMultiLevel.reduce_comm_bytes
        for the mesh scheme)."""
        return 0

    def collective_contract(self, k: int, itemsize: int = None):
        """Static communication promise for graft-prove, by execution
        mode.  ``itemsize`` defaults to the carried feature dtype's
        (graft-classes: a bf16 carriage contract promises HALF the
        ideal exchange bytes — the band scales with the class), and
        can be pinned explicitly for what-if pricing.

        The a2a routing writes explicit all-to-alls (GSPMD's
        partitioning of the sharded level compute may additionally
        lower to all-reduce/collective-permute — declared, so H1 trips
        only on a genuine surprise all-gather); the gather routing
        leaves the exchanges to GSPMD entirely; a single chip (and
        fmt='fold') is the zero-communication end of the T(c) model.
        The donated scan entry carries the features as flat param 0
        (H5)."""
        from arrow_matrix_tpu.analysis.contracts import CollectiveContract

        if itemsize is None:
            itemsize = np.dtype(self.feature_dtype or np.float32).itemsize
        single_chip = self.mesh is None or self.routing == "none"
        if single_chip:
            lowered_kinds = compiled_kinds = ()
        elif self.routing == "a2a":
            lowered_kinds = ("all-to-all",)
            compiled_kinds = ("all-to-all", "all-reduce",
                              "collective-permute")
        else:  # routing == "gather": exchanges are GSPMD's to choose
            lowered_kinds = ()
            compiled_kinds = ("all-gather", "all-reduce",
                              "collective-permute", "all-to-all")
        return CollectiveContract(
            algorithm="multi_level",
            step_bytes=self.ideal_comm_bytes(k, itemsize),
            reduce_bytes=self.reduce_comm_bytes(k, itemsize),
            repl=1,
            overlap_slabs=self.overlap_slabs,
            dtype=np.dtype(self.feature_dtype or np.float32).name
            .replace("float", "f").replace("bfloat", "bf"),
            lowered_kinds=lowered_kinds,
            compiled_kinds=compiled_kinds,
            ratio_band=(0.25, 4.0),
            donated_params=(0,),
            # One XLA loop-copy set per while body (iteration scan +
            # per-level inner scans), multiplied by the S overlap
            # sub-steps; transposes stay forbidden.  A graft-synth
            # per-tier schedule runs one bounded streaming loop per
            # scheduled tier, and the interpret lowering materializes
            # each loop's carried state (wave counter, ring cursors,
            # index-table slices, one (1, m_t, wave) accumulator tile)
            # as XLA copies — scalar/index-sized, never a (rows, k)
            # feature slab — so the budget grows by one 8-copy set per
            # scheduled tier and stays independent of n and k.
            hot_copy_budget=(16 + 8 * len(
                self.kernel_opts.get("schedule") or ()))
            * self.overlap_slabs,
            notes="flat row-major carriage: the routed a2a moves "
                  "(rows, k) slices, so the ÷c slab law lives in the "
                  "SELL feature-major executors")

    def exchange_scratch_bytes(self, k: int, itemsize: int = 4) -> int:
        """Peak per-device send+recv scratch of ONE routing exchange at
        feature width ``k`` — the a2a payload the carriage-only HBM
        model used to miss (graft-reshard satellite): a one-shot
        exchange holds both the padded send payload and the received
        copy live; a :class:`~arrow_matrix_tpu.parallel.routing
        .StagedRoute` bounds it to one stage's slice (<= the declared
        budget).  Zero for routing='gather' (GSPMD owns the exchange —
        its all-gather scratch is judged by obs/comm, not priced here)
        and on a single chip / fmt='fold' (no exchange at all)."""
        if self.routing != "a2a" or not self.fwd:
            return 0
        return max(2 * r.device_bytes_per_exchange(k, itemsize)
                   for r in list(self.fwd) + list(self.bwd))

    def predicted_hbm_bytes(self, k: int, itemsize: int = 4,
                            repl: int = 1) -> int:
        """Static per-shard HBM model for one step at feature width
        ``k``: this device's slice of every level's block stacks and
        route tables, plus the carried feature input and output
        (total_rows / n_dev rows each), plus the peak routing-exchange
        scratch (``exchange_scratch_bytes`` — the a2a send+recv
        payload; bounded by the declared budget when staged).
        obs/memview judges the compiled executable against this.
        ``repl`` is the 2.5D planning multiplier (operator + carriage
        grow exactly ×c per device at replication c on a mesh)."""
        from arrow_matrix_tpu.obs.memview import tree_device_bytes

        n_dev = self.mesh.shape[self.axis] if self.mesh is not None else 1
        ops_bytes = sum(b.device_nbytes() for b in self.blocks)
        ops_bytes += tree_device_bytes(self.fwd, self.bwd)
        base = (ops_bytes // n_dev
                + 2 * (self.total_rows // n_dev) * k * itemsize
                + self.exchange_scratch_bytes(k, itemsize))
        return base * max(int(repl), 1)

    def reshard_layout(self, repl: int = 1, tag_base: str = "multi_level"):
        """This executor's carriage as a graft-reshard
        :class:`~arrow_matrix_tpu.parallel.reshard.Layout`: padded rows
        in level-0 order, sharded over the mesh's block axis.  ``repl``
        is the replica-expanded view for planned 2.5D growth.  The
        carried row order is
        ``self.perm0`` — redistribution_plan's ``perm_map`` between two
        executors of the same problem is
        ``inv_perm0_src[perm0_dst]`` masked to real rows."""
        from arrow_matrix_tpu.parallel.reshard import Layout, layout_tag

        n_dev = self.mesh.shape[self.axis] if self.mesh is not None else 1
        lay = Layout(total_rows=int(self.total_rows), n_dev=int(n_dev),
                     repl=max(int(repl), 1))
        return Layout(total_rows=lay.total_rows, n_dev=lay.n_dev,
                      repl=lay.repl, tag=layout_tag(tag_base, lay))

    def carriage_hbm_bytes(self, k: int, itemsize: int = 4,
                           repl: int = 1) -> int:
        """Incremental per-shard carriage bytes a feature width ``k``
        adds on top of the resident operator (``predicted_hbm_bytes(k)
        - predicted_hbm_bytes(0)``): the marginal cost of admitting one
        more request against an executor whose operator stays
        HBM-resident across requests — graft-serve's admission price
        (obs/memview.request_bytes_for)."""
        return (self.predicted_hbm_bytes(k, itemsize, repl)
                - self.predicted_hbm_bytes(0, itemsize, repl))

    def shard_report(self) -> dict:
        """Load report over the layout's compute units — block rows for
        arrow levels (contiguous runs of which form the device shards,
        so block-row skew bounds device skew), tiers under fmt='fold'
        (obs/imbalance.py schema)."""
        from arrow_matrix_tpu.obs.imbalance import summarize_units

        rows: list = []
        nnz: list = []
        slots: list = []
        for blk in self.blocks:
            st = _block_unit_stats(blk)
            rows.extend(int(v) for v in st["rows"])
            nnz.extend(int(v) for v in st["nnz"])
            slots.extend(int(v) for v in st["slots"])
        units = "tier" if self.folded else "block-row"
        return summarize_units(rows, nnz, slots, units=units)

    def run(self, x: jax.Array, iterations: int,
            donate: bool = False) -> jax.Array:
        """``iterations`` steps as ONE device program (`lax.scan` over
        the jitted step): a single dispatch regardless of iteration
        count — the iteration loop itself is compiler-friendly control
        flow on device, not a host loop of dispatches (which pays
        dispatch latency per step).

        ``donate=True`` donates the input buffer to the scan carry, so
        only ONE carried feature buffer is resident during the loop
        (the 2^27 single-chip HBM budget depends on it; the donated
        ``x`` is dead afterwards — callers that reuse it must copy
        first).  CPU ignores donation with a warning; TPU aliases.
        """
        fn = self._scan_steps_donated if donate else self._scan_steps
        return fn(x, self.fwd, self.bwd, self.blocks, n=iterations)


def _block_unit_stats(blk) -> dict:
    """Per-unit (rows, nnz, slots) of one level's packed operator,
    dispatched on its layout type (arrow block grid / SELL tiers) —
    shared by ``MultiLevelArrow.shard_report`` and
    ``arrow_layout.arrow_blocks_shard_report``."""
    from arrow_matrix_tpu.ops.arrow_blocks import block_row_stats
    from arrow_matrix_tpu.ops.sell import SellMatrix, sell_stats

    if isinstance(blk, ArrowBlocks):
        return block_row_stats(blk)
    if isinstance(blk, SellMatrix):
        return sell_stats(blk)
    raise TypeError(f"no unit stats for {type(blk).__name__}")


def resolve_chunk(chunk, blk: ArrowBlocks, total_rows: int, k: int,
                  gather_budget: int):
    """Static per-level slot-chunk: pass explicit values through,
    resolve "auto" from the level's ELL slot count and the gather
    budget (all trace-time constants)."""
    if chunk != "auto":
        return chunk
    if blk.fmt != "ell":
        return None
    from arrow_matrix_tpu.ops.ell import auto_chunk

    dims = [blk.diag_cols.shape[-1], blk.col_cols.shape[-1]]
    if not blk.head_flat:   # flat head scatters; chunking is ELL-only
        dims.append(blk.head_cols.shape[-1])
    if blk.banded:
        dims += [blk.lo_cols.shape[-1], blk.hi_cols.shape[-1]]
    return auto_chunk(total_rows, k, max(dims), gather_budget)


def multi_level_spmm(x: jax.Array, fwd, bwd,
                     blocks: Sequence[ArrowBlocks], widths: tuple,
                     chunk="auto", kernel: str = "xla",
                     gather_budget: int = 1 << 30,
                     mesh: Optional[Mesh] = None,
                     axis: str = "blocks", layout: str = "slim",
                     arm_axis: str = "arm",
                     overlap_slabs: int = 1) -> jax.Array:
    """One decomposition-wide SpMM (jitted; K unrolled — K is small).

    Forward feature propagation (reference
    _propagate_features_forwards, arrow_dec_mpi.py:507-550), per-level
    arrow SpMM, backward aggregation (reference
    _aggregate_features_backwards, arrow_dec_mpi.py:404-440).
    ``x`` is flat (total_rows, k); each level reshapes to its own
    blocking (nb_i, w_i, k).  ``kernel="pallas"`` routes dense-format
    levels through the fused Pallas kernels — directly on a single
    chip, per shard under shard_map on a mesh.
    """
    from arrow_matrix_tpu.parallel.routing import take as routed_or_take

    if overlap_slabs > 1:
        # Chunked overlap schedule (graft-stream): each feature
        # sub-slab runs the full level chain independently, so slab
        # i+1's routing exchange is free to fly while slab i's level
        # SpMMs run.  Flat carriage is row-major: the feature axis is
        # axis 1.  Bit-identical f32 — per-element addends never
        # regroup.
        from arrow_matrix_tpu.parallel.routing import overlap_slices

        outs = []
        for j, (lo, hi) in enumerate(
                overlap_slices(x.shape[1], overlap_slabs)):
            with jax.named_scope(f"overlap_slab_{j}"):
                outs.append(multi_level_spmm(
                    x[:, lo:hi], fwd, bwd, blocks, widths=widths,
                    chunk=chunk, kernel=kernel,
                    gather_budget=gather_budget, mesh=mesh, axis=axis,
                    layout=layout, arm_axis=arm_axis))
        return jnp.concatenate(outs, axis=1)

    total, k = x.shape
    k_levels = len(blocks)
    partials = []
    x_cur = x
    for i in range(k_levels):
        if i > 0:
            with jax.named_scope(f"route_forward_{i}"):
                x_cur = routed_or_take(x_cur, fwd[i - 1], mesh, axis)
        with jax.named_scope(f"level_{i}_spmm"):
            w = widths[i]
            xb = x_cur.reshape(total // w, w, k)
            use_pallas = False
            if kernel == "pallas" and blocks[i].fmt == "dense":
                from arrow_matrix_tpu.ops import pallas_blocks

                # Oversized levels (grown last-level width) whose
                # feature operands exceed VMEM fall back to XLA per
                # level.
                use_pallas = pallas_blocks.feasible(w, k,
                                                    blocks[i].banded)
            if layout == "wide" and mesh is not None:
                # Wide layout per level: row-arm devices compute the
                # head row + reduce, column-arm devices the diag/col/
                # banded blocks — disjoint groups overlapping in space
                # (reference ArrowMPI composed into the orchestrator,
                # arrow_dec_mpi.py:134).  Output slice 0 of the arm
                # axis holds the product.
                from arrow_matrix_tpu.parallel.arrow_layout import (
                    wide_step_shard_map,
                )

                wstep = wide_step_shard_map(
                    blocks[i], mesh, arm_axis=arm_axis, block_axis=axis,
                    chunk=resolve_chunk(chunk, blocks[i], total, k,
                                        gather_budget))
                c = wstep(blocks[i], xb)[0]
            elif use_pallas and mesh is not None:
                # Pallas custom calls do not partition under GSPMD, but
                # the shard-local shapes under shard_map are static:
                # run the slim step body per shard with the fused
                # kernels inside and the usual psum/ppermute
                # collectives around them.
                from arrow_matrix_tpu.parallel.arrow_layout import (
                    slim_step_shard_map,
                )

                step = slim_step_shard_map(blocks[i], mesh, axis=axis,
                                           kernel="pallas")
                c = step(blocks[i], xb)
            elif use_pallas:
                c = pallas_blocks.arrow_spmm_pallas(blocks[i], xb)
            else:
                c = arrow_spmm(blocks[i], xb,
                               chunk=resolve_chunk(chunk, blocks[i],
                                                   total, k,
                                                   gather_budget))
            partials.append(c.reshape(total, k))

    with jax.named_scope("aggregate_backward"):
        agg = partials[-1]
        for i in range(k_levels - 1, 0, -1):
            agg = partials[i - 1] + routed_or_take(agg, bwd[i - 1],
                                                   mesh, axis)
    return agg
