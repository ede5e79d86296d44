"""Shared CLI plumbing: flag parsing helpers, platform selection, and
matrix loading.

The reference's per-entry-point argparse + ``str2bool`` + device-string
convention (reference arrow/common/utils.py:9-17, scripts/*_main.py) —
plus the one genuinely TPU-specific concern: the JAX platform must be
pinned *before* the first backend initialization.
"""

from __future__ import annotations

import argparse
import os
from typing import Optional

import numpy as np
from scipy import sparse


def str2bool(v) -> bool:
    """Reference-compatible boolean flag parser (utils.py:9-17)."""
    if isinstance(v, bool):
        return v
    if v.lower() in ("yes", "true", "t", "y", "1"):
        return True
    if v.lower() in ("no", "false", "f", "n", "0"):
        return False
    raise argparse.ArgumentTypeError("Boolean value expected.")


def add_device_args(parser: argparse.ArgumentParser) -> None:
    parser.add_argument(
        "-i", "--device", type=str, default="auto",
        choices=["auto", "cpu", "tpu"],
        help="Compute platform (the reference's cpu/gpu gate, "
             "spmm_arrow_main.py:18; 'auto' uses the default backend, "
             "the TPU where one is attached).")
    parser.add_argument(
        "--devices", type=int, default=0,
        help="Force an N-device virtual CPU platform (multi-chip layouts "
             "without hardware; the analog of mpiexec --oversubscribe). "
             "Implies --device cpu.")


def add_distributed_args(parser: argparse.ArgumentParser) -> None:
    """Multi-process launch flags (the mpiexec-rank analog: one OS
    process per host, `jax.distributed` joins them into one runtime).

    Launch N processes with the same --coordinator/--num-processes and
    distinct --process-id 0..N-1; on TPU pods the three are
    auto-detected and none is needed.
    """
    parser.add_argument(
        "--coordinator", type=str, default=None,
        help="host:port of process 0's coordination service; enables "
             "multi-process execution (jax.distributed.initialize).")
    parser.add_argument("--num-processes", type=int, default=None)
    parser.add_argument("--process-id", type=int, default=None)


def setup_platform(args: argparse.Namespace) -> None:
    """Pin the JAX platform per --device/--devices, and join the
    multi-process runtime when --coordinator is given (must run before
    anything initializes a JAX backend).  Runs that may reach the chip
    share the persistent compile cache; CPU-pinned runs (tests,
    rehearsals) compile fresh."""
    from arrow_matrix_tpu.utils.platform import (
        enable_compile_cache,
        force_cpu_devices,
    )

    coordinator = getattr(args, "coordinator", None)
    cpu = args.device == "cpu" or args.devices > 0
    if not cpu:
        enable_compile_cache()
    if coordinator is not None:
        from arrow_matrix_tpu.parallel.mesh import initialize_multihost

        if cpu:
            # Pin + gloo even without an explicit count (--device cpu
            # alone must behave like the single-process path).
            import jax

            force_cpu_devices(args.devices if args.devices > 0 else None)
            jax.config.update("jax_cpu_collectives_implementation",
                              "gloo")
        initialize_multihost(coordinator, args.num_processes,
                             args.process_id)
    elif cpu:
        force_cpu_devices(args.devices if args.devices > 0 else None)
    if args.device == "tpu":
        # An explicit TPU request is a requirement, not a preference:
        # never run it on whatever backend came up instead.
        import jax

        found = jax.devices()[0].platform
        if found != "tpu":
            raise SystemExit(f"--device tpu but JAX's backend is "
                             f"{found!r}: no TPU here")


def add_heal_args(parser: argparse.ArgumentParser,
                  checkpoint_every_default: int = 10) -> None:
    """graft-heal run-loop flags, shared by all three SpMM CLIs: the
    supervised iteration loop (watchdog / bounded retry / finite-check)
    plus iteration-state checkpointing (``utils/checkpoint.py``)."""
    g = parser.add_argument_group(
        "graft-heal", "supervised run loop: watchdog, bounded retry, "
                      "checkpoint resume (see faults/)")
    g.add_argument("--checkpoint", type=str, default=None,
                   help="Directory/base for iteration-state checkpoints "
                        "(requires --carry): X and the iteration "
                        "counter are saved every --checkpoint_every "
                        "iterations (orbax when available — sharded "
                        "arrays persist per-shard without a host "
                        "gather) and the run resumes from the "
                        "checkpoint when one exists.  Beyond reference "
                        "parity: the reference's only resume point is "
                        "the decomposition artifact.")
    g.add_argument("--checkpoint_every", type=int,
                   default=checkpoint_every_default)
    g.add_argument("--watchdog", type=float, default=0.0,
                   help="Per-iteration watchdog seconds (0 disables): "
                        "an iteration exceeding the budget is treated "
                        "as a fault — retried from its entry state, or "
                        "escalated to process-level recovery when it "
                        "never drains.")
    g.add_argument("--max_retries", type=int, default=2,
                   help="Consecutive faulted attempts of one iteration "
                        "before the run fails (each retry backs off "
                        "exponentially and rolls back to the last "
                        "checkpoint when one exists).")
    g.add_argument("--retry_jitter", type=float, default=0.0,
                   help="±fraction of deterministic, seedable jitter "
                        "on each backoff delay (faults/policy.py): 0 "
                        "keeps the bare exponential schedule; serving "
                        "deployments use ~0.2 so retries across "
                        "tenants don't synchronize.")
    g.add_argument("--finite_check", type=str2bool, nargs="?",
                   default=True, const=True,
                   help="Jitted all-finite check on the carried X each "
                        "iteration; NaN/Inf rolls back to the last "
                        "checkpoint instead of silently poisoning "
                        "every subsequent iteration (carry mode only).")


def make_supervisor(args: argparse.Namespace, name: str, *,
                    carry: bool, layout: Optional[str] = None,
                    registry=None, canonicalize=None):
    """Build the graft-heal Supervisor for a CLI run from its flags
    (one recipe so all three CLIs agree on flag semantics).

    ``canonicalize`` is the executor's checkpoint canonicalizer — for
    2.5D replicated runs (graft-repl) pass its ``merge_carries`` so
    saves persist the merged carriage instead of replica 0's partial
    slab view.
    """
    from arrow_matrix_tpu.faults import RetryPolicy, Supervisor

    return Supervisor(
        name, carry=carry,
        policy=RetryPolicy.from_args(args),
        checkpoint_path=getattr(args, "checkpoint", None),
        checkpoint_every=getattr(args, "checkpoint_every", 0),
        finite_check=bool(getattr(args, "finite_check", True)) and carry,
        layout=layout, registry=registry, canonicalize=canonicalize)


def load_sparse_matrix(path: str, dtype=np.float32) -> sparse.csr_matrix:
    """Load a sparse matrix from .npz (scipy), .mtx (matrix market), or
    .mat (matlab; the reference's primary input format,
    decomposition_main.py:18-34) — dispatch on extension."""
    ext = os.path.splitext(path)[1].lower()
    if ext == ".npz":
        m = sparse.load_npz(path)
    elif ext in (".mtx", ".mm"):
        from scipy.io import mmread

        m = mmread(path)
    elif ext == ".mat":
        m = _load_matlab(path)
    else:
        raise ValueError(f"unsupported matrix format {ext!r} "
                         f"(expected .npz, .mtx, or .mat)")
    m = sparse.csr_matrix(m).astype(dtype)
    m.sum_duplicates()
    m.sort_indices()
    return m


def _load_matlab(path: str) -> sparse.spmatrix:
    from scipy.io import loadmat

    try:
        contents = loadmat(path)
    except NotImplementedError:
        # v7.3 files are HDF5 (the reference reads them with mat73,
        # decomposition_main.py:18-34; mat73 is not in this image).
        return _load_matlab_hdf5(path)
    for v in contents.values():
        if sparse.issparse(v):
            return v
    raise ValueError(f"no sparse matrix found in {path}")


def _load_matlab_hdf5(path: str) -> sparse.spmatrix:
    """MATLAB v7.3 (HDF5) sparse loader via h5py.

    MATLAB stores a sparse matrix as an HDF5 group with CSC component
    datasets ``data``/``ir``/``jc`` and the row count in the group's
    ``MATLAB_sparse`` attribute.  The SuiteSparse collection (the
    reference's primary datasets) keeps the matrix at ``Problem/A``;
    that location is probed first, then any sparse-tagged group.
    """
    try:
        import h5py
    except ImportError:
        raise ValueError(
            f"{path} is a MATLAB v7.3 (HDF5) file and h5py is not "
            f"available; convert it to .npz or .mtx first")

    def as_csc(node):
        jc = np.asarray(node["jc"], dtype=np.int64)
        ir = np.asarray(node["ir"], dtype=np.int64)
        data = (np.asarray(node["data"]) if "data" in node
                else np.ones(ir.size, dtype=np.float32))
        n_rows = int(node.attrs["MATLAB_sparse"])
        n_cols = jc.size - 1
        return sparse.csc_matrix((data, ir, jc), shape=(n_rows, n_cols))

    with h5py.File(path, "r") as f:
        if "Problem" in f and "A" in f["Problem"] \
                and "MATLAB_sparse" in f["Problem"]["A"].attrs:
            return as_csc(f["Problem"]["A"])
        found = []

        def visit(name, node):
            if isinstance(node, h5py.Group) and "MATLAB_sparse" in node.attrs:
                found.append(name)

        f.visititems(visit)
        if found:
            return as_csc(f[found[0]])
    raise ValueError(f"no MATLAB sparse matrix found in HDF5 file {path}")


def random_adjacency(vertices: int, edges: int, seed: int,
                     dtype=np.float32) -> sparse.csr_matrix:
    """Random graph with ~edges nonzeros (the reference's random dataset
    path, spmm_15d_main.py:100-110 via utils.generate_sparse_matrix)."""
    from arrow_matrix_tpu.utils.graphs import random_csr

    nnz_per_row = max(1, edges // max(vertices, 1))
    return random_csr(vertices, vertices, nnz_per_row, seed=seed).astype(dtype)


def normalize_scale(a: sparse.csr_matrix) -> sparse.csr_matrix:
    """Scale so iterated SpMM stays bounded (benchmark loops reuse the
    output as the next input)."""
    s = max(abs(a).sum(axis=1).max(), 1.0)
    return (a / s).tocsr().astype(a.dtype)
