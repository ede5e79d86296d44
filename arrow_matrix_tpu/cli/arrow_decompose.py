"""``arrow_decompose`` — offline arrow decomposition CLI.

Counterpart of the reference's decomposition entry point
(reference scripts/decomposition_main.py:109-208): load a graph, run
``arrow_decomposition``, save the npy-triplet artifact.  Flags mirror
the reference's (``:121-137``); ``--format`` is inferred from the file
extension here instead of being a separate flag.
"""

from __future__ import annotations

import argparse
import os
import pickle
import time

import numpy as np

from arrow_matrix_tpu.cli.common import load_sparse_matrix, str2bool


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(
        description="Arrow decomposition of sparse graphs.")
    parser.add_argument("--width", type=int, default=5_000_000,
                        help="Arrow width (block size).")
    parser.add_argument("--dataset_dir", type=str, default=".",
                        help="Directory containing the graph files.")
    parser.add_argument("--dataset_name", nargs="+", type=str, required=True,
                        help="Graph file names (extension included; "
                             ".npz/.mtx/.mat).")
    parser.add_argument("--levels", type=int, default=10,
                        help="Maximum number of decomposition levels "
                             "(the reference hardcodes 10, "
                             "decomposition_main.py:184).")
    parser.add_argument("--block_diagonal", type=str2bool, nargs="?",
                        default=True, const=True,
                        help="Block-diagonal (vs banded) edge criterion.")
    parser.add_argument("--directed", type=str2bool, nargs="?", default=False, const=True,
                        help="Accepted for reference flag parity; the "
                             "decomposer handles asymmetric inputs "
                             "automatically (structural symmetrization "
                             "for linearization only).")
    parser.add_argument("--seed", type=int, default=0,
                        help="Linearization RNG seed.")
    parser.add_argument("--visualize", type=str2bool, nargs="?",
                        default=False, const=True,
                        help="Save a spy plot of each level "
                             "(decomposition_main.py:83-106).")
    parser.add_argument("--save_input_graph", type=str2bool, nargs="?",
                        default=False, const=True,
                        help="Pickle the parsed input graph next to the "
                             "artifact to skip re-parsing "
                             "(decomposition_main.py:157-162).")
    parser.add_argument("--out_dir", type=str, default=None,
                        help="Output directory (default: dataset_dir).")
    parser.add_argument("--band_detect", type=str2bool, nargs="?",
                        default=True, const=True,
                        help="Detect banded/bandable inputs (identity "
                             "or RCM order) and emit ONE level with "
                             "zero routing; false restores the plain "
                             "recursion (e.g. to regenerate legacy "
                             "multi-level artifacts).")
    parser.add_argument("--backend", type=str, default="auto",
                        choices=["auto", "native", "numpy"],
                        help="Linearization backend: native C++ kernels "
                             "(the reference's fast Julia decomposer "
                             "role) or the scipy/csgraph implementation. "
                             "Backends use different RNG streams: pin "
                             "one for seed-reproducible results across "
                             "machines.")
    return parser


def decompose_one(path: str, args: argparse.Namespace) -> None:
    from arrow_matrix_tpu.decomposition import arrow_decomposition
    from arrow_matrix_tpu.io import save_decomposition

    base_name = os.path.splitext(os.path.basename(path))[0]
    out_dir = args.out_dir or args.dataset_dir
    os.makedirs(out_dir, exist_ok=True)
    base = os.path.join(out_dir, base_name)

    # The cache is only honored when --save_input_graph opted into it,
    # and only while it is at least as new as the source file (a stale
    # pickle must never silently replace an updated input graph; pickle
    # is also an arbitrary-code-execution format, so loading one the
    # user never asked to create is not acceptable).
    cache = base + ".pickle"
    # Strict >: a source rewrite landing within the filesystem's
    # timestamp granularity of the cache write must invalidate (same
    # tie-break direction as the native-library staleness check).
    cache_fresh = (args.save_input_graph and os.path.exists(cache)
                   and (not os.path.exists(path)
                        or os.path.getmtime(cache) > os.path.getmtime(path)))
    if cache_fresh:
        print(f"loading cached graph {cache}")
        with open(cache, "rb") as f:
            a = pickle.load(f)
    else:
        print(f"loading {path}")
        a = load_sparse_matrix(path)
        if args.save_input_graph:
            with open(cache, "wb") as f:
                pickle.dump(a, f)

    print(f"decomposing n={a.shape[0]} nnz={a.nnz} width={args.width} "
          f"levels<={args.levels} block_diagonal={args.block_diagonal}")
    tic = time.perf_counter()
    # Directed graphs need no special flag: the decomposer symmetrizes
    # the structural pattern internally for linearization (the Julia
    # reference's `symmetric` pre-step, ArrowDecomposition.jl:119-124)
    # while the level matrices keep the asymmetric values.
    levels = arrow_decomposition(
        a, arrow_width=args.width, max_levels=args.levels,
        block_diagonal=args.block_diagonal, seed=args.seed,
        backend=args.backend, band_detect=args.band_detect)
    print(f"decomposed into {len(levels)} levels in "
          f"{time.perf_counter() - tic:.1f}s; achieved widths "
          f"{[l.arrow_width for l in levels]}")

    save_decomposition(levels, base, block_diagonal=args.block_diagonal)
    print(f"saved artifact under {base}_B_{levels[0].arrow_width}_*")

    if args.visualize:
        visualize(levels, base)


def visualize(levels, base: str) -> None:
    """Spy-plot each level (reference
    visualize_banded_decomposition, decomposition_main.py:83-106)."""
    import matplotlib

    matplotlib.use("Agg")
    import matplotlib.pyplot as plt

    fig, axes = plt.subplots(1, len(levels),
                             figsize=(4 * len(levels), 4), squeeze=False)
    for ax, lvl in zip(axes[0], levels):
        ax.spy(lvl.matrix, markersize=0.1)
        ax.set_title(f"width {lvl.arrow_width}")
    fig.savefig(base + "_decomposition.png", dpi=150, bbox_inches="tight")
    plt.close(fig)
    print(f"wrote {base}_decomposition.png")


def main(argv=None) -> None:
    args = build_parser().parse_args(argv)
    for name in args.dataset_name:
        decompose_one(os.path.join(args.dataset_dir, name), args)


if __name__ == "__main__":
    main()
