"""``spmm_petsc`` — 1-D row-partition (PETSc-style) baseline benchmark.

Counterpart of the reference's PETSc baseline entry point
(reference scripts/spmm_petsc_main.py + arrow/baseline/spmm_petsc.py:
398-495).  The reference loads pre-partitioned per-rank slice files
(``{name}.part.{P}.slice.{r}.npz``); here there is one SPMD process, so
``--file`` takes the whole matrix (or a ``.part.`` slice-scheme prefix,
reassembled) and the partition is computed at load.  ``--dryrun`` builds
the exchange tables and exits without benchmarking
(spmm_petsc_main.py:40).
"""

from __future__ import annotations

import argparse
import glob
import os
import re
import time

import numpy as np
from scipy import sparse

from arrow_matrix_tpu.cli.common import (
    add_device_args,
    add_distributed_args,
    add_heal_args,
    load_sparse_matrix,
    make_supervisor,
    normalize_scale,
    random_adjacency,
    setup_platform,
    str2bool,
)


#: The reference's slice-file naming scheme (spmm_petsc.py:82-102) —
#: ONE copy shared by the per-slice fast path and the reassembly
#: fallback, so both always agree on what matches.
SLICE_RE = re.compile(r"(.*)\.part\.(\d+)\.slice\.(\d+)\.npz$")


def load_slices_or_matrix(path: str) -> sparse.csr_matrix:
    """Accept either one matrix file or any slice of the reference's
    ``{name}.part.{P}.slice.{r}.npz`` scheme (all slices are then
    reassembled; the partition itself is recomputed)."""
    m = SLICE_RE.match(path)
    if not m:
        return load_sparse_matrix(path)
    base, p = m.group(1), int(m.group(2))
    paths = sorted(
        glob.glob(f"{base}.part.{p}.slice.*.npz"),
        key=lambda s: int(re.search(r"slice\.(\d+)\.npz$", s).group(1)))
    if len(paths) != p:
        raise SystemExit(f"found {len(paths)} of {p} slice files for {base}")
    return sparse.vstack([sparse.load_npz(f) for f in paths]).tocsr()


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(description="SpMM PETSc benchmark.")
    parser.add_argument("-s", "--seed", type=int, default=42)
    parser.add_argument("-f", "--file", type=str, default=None,
                        help="Matrix file, or one slice of the "
                             "reference's .part.P.slice.r.npz scheme.")
    parser.add_argument("-v", "--vertices", type=int, default=100_000,
                        help="Vertices of the random matrix (no --file).")
    parser.add_argument("-e", "--edges", type=int, default=1_000_000)
    parser.add_argument("-c", "--columns", type=int, default=32)
    parser.add_argument("-z", "--iterations", type=int, default=3)
    parser.add_argument("--validate", type=str2bool, nargs="?", default=True, const=True)
    parser.add_argument("--dryrun", type=str2bool, nargs="?", default=False, const=True,
                        help="Build the exchange tables, print their "
                             "stats, skip the benchmark.")
    parser.add_argument("-m", "--memory", type=float, default=0.5,
                        help="Fraction of currently-FREE device memory "
                             "(net of this layout's own blocks) "
                             "budgeted for kernel intermediates; "
                             "drives the ELL slot-chunk auto-tiling "
                             "(the reference's --memory OOM-model GPU "
                             "tiling, spmm_petsc.py:323-395).  <= 0 "
                             "disables chunking.")
    parser.add_argument("--carry", type=str2bool, nargs="?",
                        default=False, const=True,
                        help="Carry X across iterations (X := A @ X "
                             "propagation; the 1-D row partition "
                             "preserves the blocked layout, so the "
                             "result feeds the next step directly) "
                             "instead of timing the same input.")
    add_heal_args(parser)
    parser.add_argument("--logdir", type=str, default="./logs")
    parser.add_argument("--comm_report", type=str2bool, nargs="?",
                        default=False, const=True,
                        help="Account the per-iteration collective "
                             "bytes of the compiled step from its HLO "
                             "(compare against spmm_arrow's modes — "
                             "the reference paper's headline metric).")
    parser.add_argument("--mem_report", type=str2bool, nargs="?",
                        default=False, const=True,
                        help="Report the compiled step's per-device "
                             "memory breakdown against the format-"
                             "metadata prediction, plus the per-shard "
                             "load-imbalance report.")
    add_device_args(parser)
    add_distributed_args(parser)
    return parser


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    if args.checkpoint and not args.carry:
        # Pure flag error: fail before any build/compile work.
        raise SystemExit("--checkpoint requires --carry (there is no "
                         "iteration state to resume when X is the "
                         "same input every iteration)")
    setup_platform(args)

    import jax

    from arrow_matrix_tpu.parallel.mesh import make_mesh
    from arrow_matrix_tpu.parallel.spmm_1d import MatrixSlice1D
    from arrow_matrix_tpu.utils import logging as wb
    from arrow_matrix_tpu.utils.graphs import random_dense

    n_dev = len(jax.devices())
    mesh = make_mesh((n_dev,), ("slices",))

    # Per-slice ingest (the reference's IO-parallel loading: each rank
    # reads only its own slice file, spmm_petsc.py:421-440) whenever
    # the slice count matches the device count; otherwise the slices
    # are reassembled into one host view (the partition is recomputed).
    slice_paths = None
    owned_slabs: dict = {}
    if args.file:
        m = SLICE_RE.match(args.file)
        if m and int(m.group(2)) == n_dev:
            base, p = m.group(1), int(m.group(2))
            slice_paths = [f"{base}.part.{p}.slice.{r}.npz"
                           for r in range(p)]
            missing = [q for q in slice_paths if not os.path.exists(q)]
            if missing:
                raise SystemExit(f"missing slice files: {missing[:3]}")
        name = os.path.basename(args.file)
    else:
        name = f"random_{args.vertices}_{args.edges}"

    if slice_paths is not None:
        from arrow_matrix_tpu.parallel.spmm_1d import (
            _exchange_sum,
            _owned_slice_ids,
            _primary_slice_ids,
        )

        mine = sorted(_owned_slice_ids(mesh, "slices"))
        primary = _primary_slice_ids(mesh, "slices")
        owned_slabs = {
            d: sparse.load_npz(slice_paths[d]).tocsr().astype(np.float32)
            for d in mine}
        # Global normalize_scale from per-slice row sums (each process
        # reads only its own slices; one host-side max exchange with
        # one contributor per slice).
        scales = np.zeros(n_dev)
        for d, s in owned_slabs.items():
            if s.nnz and d in primary:
                scales[d] = float(abs(s).sum(axis=1).max())
        scale = max(float(np.max(_exchange_sum(scales))), 1.0)
        for d in mine:
            owned_slabs[d] = (owned_slabs[d] / scale).tocsr()
        a = [(lambda d=d: owned_slabs[d]) if d in owned_slabs
             else slice_paths[d] for d in range(n_dev)]
    elif args.file:
        a = normalize_scale(load_slices_or_matrix(args.file))
    else:
        a = normalize_scale(
            random_adjacency(args.vertices, args.edges, args.seed))

    wb.init("PETSc_TPU_v1", name, config=vars(args))

    with wb.segment("build_time"):
        dist = MatrixSlice1D(
            a, mesh,
            chunk="auto" if args.memory > 0 else None,
            memory_fraction=args.memory if args.memory > 0 else 0.5)
    print(f"{n_dev} slices of <= {dist.l_rows} rows; exchange slot "
          f"{dist.slot} rows/pair")
    if args.dryrun:
        wb.finish(args.logdir)
        return 0

    x_host = random_dense(dist.n, args.columns, seed=args.seed)
    x = dist.set_features(x_host)

    if args.validate:
        got = dist.gather_result(dist.spmm(x))
        if slice_paths is not None:
            # Per-slice golden: each process validates the rows of the
            # slices it loaded (the global matrix never exists here).
            err_n = err_d = 0.0
            ok = True
            for d, slab in owned_slabs.items():
                lo, hi = dist.slices[d]
                want_d = np.asarray(slab @ x_host)
                err_n += float(np.linalg.norm(got[lo:hi] - want_d) ** 2)
                err_d += float(np.linalg.norm(want_d) ** 2)
                # Elementwise gate per owned slab: the reassembled path
                # checks np.allclose, and a single bad row can hide
                # inside a small Frobenius ratio — both --validate
                # paths must enforce the same strictness (ADVICE r3).
                ok &= bool(np.allclose(got[lo:hi], want_d,
                                       rtol=1e-4, atol=1e-4))
            err = (err_n / max(err_d, 1e-30)) ** 0.5
            ok = ok and bool(err < 1e-4)
            scope = (f"rows of slices {sorted(owned_slabs)}"
                     if jax.process_count() > 1 else "all rows")
            print(f"validation ({scope}): allclose={ok} "
                  f"rel frobenius err={err:.3e}")
        else:
            want = np.asarray(a @ x_host)
            err = np.linalg.norm(got - want) / max(np.linalg.norm(want),
                                                   1e-30)
            ok = np.allclose(got, want, rtol=1e-4, atol=1e-4)
            print(f"validation: allclose={ok} rel frobenius err={err:.3e}")
        wb.log({"frobenius_err": float(err)})
        if not ok:
            wb.finish(args.logdir)
            return 1

    y = dist.spmm(x)  # compile + warmup
    jax.block_until_ready(y)
    if args.comm_report:
        from arrow_matrix_tpu import obs
        from arrow_matrix_tpu.utils import commstats

        rep = obs.account_collectives(
            "spmm_1d", dist._step, dist.l_cols, dist.l_data,
            dist.nl_cols, dist.nl_data, dist.send_idx, x,
            ideal_bytes=obs.ideal_bytes_for(dist, args.columns))
        print(f"per-iteration collective bytes ({rep['source']} HLO):")
        print(commstats.format_stats(rep["collectives"]))
        if rep["ratio"] is not None:
            print(f"measured vs paper-model ideal: "
                  f"{rep['measured_bytes']} / {rep['ideal_bytes']} "
                  f"bytes = {rep['ratio']:.2f}x")
    if args.mem_report:
        from arrow_matrix_tpu import obs

        mem = obs.account_memory(
            "spmm_1d", dist._step, dist.l_cols, dist.l_data,
            dist.nl_cols, dist.nl_data, dist.send_idx, x,
            predicted_bytes=obs.predicted_bytes_for(dist, args.columns))
        print(obs.format_memory_report(mem))
        imb = obs.account_imbalance("spmm_1d", dist)
        if imb is not None:
            print(obs.format_imbalance_report(imb))
    sup = make_supervisor(args, "spmm_petsc", carry=args.carry,
                          layout="petsc/1d_sliced")
    start_it = 0
    if args.carry and args.checkpoint:
        state = sup.resume(like=x)
        if state is not None:
            x, start_it = state
            print(f"resumed from {args.checkpoint} at iteration "
                  f"{start_it}")

    def body(xb, it):
        wb.set_iteration_data({"iteration": it})
        tic = time.perf_counter()
        yb = dist.spmm(xb)
        jax.block_until_ready(yb)
        wb.log({"spmm_time": time.perf_counter() - tic})
        # 1-D row partition preserves the blocked layout: the result
        # is directly the next carried state.
        return yb

    _, ok = sup.run(body, x, start_it, args.iterations)

    s = wb.get_log().summarize().get("spmm_time")
    if s:
        print(f"spmm_time mean {s['mean'] * 1e3:.3f} ms over "
              f"{s['count']} iterations (min {s['min'] * 1e3:.3f})")
    out = wb.finish(args.logdir)
    if out:
        print(f"log written to {out}.json")
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
