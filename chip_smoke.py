#!/usr/bin/env python3
"""Chip smoke: drive the arrow SpMM main path once on a TPU and check it.

One process holds the chip for the whole run.  Phases (one chip, the
default):

  workload  Barabasi-Albert graph n = 2^22, m = 8 (seeded), arrow width
            2048, block-diagonal decomposition with the native
            decomposer, saved under bench_cache/chip_smoke/.
  main      ``spmm_arrow.main`` on the saved artifact at k = 16 and
            k = 128 (no --fmt: auto-selects fold on one chip), 5
            iterations each with --validate against the scipy golden.
  fused     the same operator through ``MultiLevelArrow(fmt="fold",
            kernel="pallas_sell")`` at k = 16 and 128 in f32 and bf16
            carriage, compared with the XLA fold result and the golden;
            the compiled step must hold ``tpu_custom_call``.  Each
            kernel's first call is split into trace + lowering,
            compile, and the first run.

``--chips 4`` runs only the mesh path: ``spmm_arrow.main`` on all four
devices (auto-selects sell + a2a) at k = 16 and 128 with --validate,
and checks that every device holds part of the operator.  Its default
graph is n = 2^21, the size run on four chips (PERF.md, PR 21).

Every phase prints its seconds and the device's peak bytes in use.  The
last line of stdout is ``{"ok": true, "device": {...}}``; any failure
exits non-zero without it, and so does a run that finds no TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ARROW_WIDTH = 2048      # the protocol width (BASELINE.md, bench.py)
BA_NEIGHBORS = 8
FEATURES = (16, 128)
ITERATIONS = 5


def log(msg: str) -> None:
    print(msg, flush=True)


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(1)


def peak_bytes(devices) -> str:
    """Per-device ``peak_bytes_in_use`` (and bytes in use) as text."""
    parts = []
    for d in devices:
        st = d.memory_stats() or {}
        parts.append(f"{d.id}: peak {st.get('peak_bytes_in_use')} "
                     f"in_use {st.get('bytes_in_use')}")
    return "; ".join(parts)


class Phase:
    """Times one phase and reports the devices' memory after it."""

    def __init__(self, name: str, devices):
        self.name, self.devices = name, devices

    def __enter__(self):
        self.t0 = time.perf_counter()
        log(f"[{self.name}] start")
        return self

    def __exit__(self, *exc):
        self.seconds = time.perf_counter() - self.t0
        log(f"[{self.name}] {self.seconds:.3f} s; memory "
            f"{peak_bytes(self.devices)}")
        return False


def build_workload(n: int, seed: int, out_dir: str, devices) -> tuple:
    """Generate + decompose once and save the artifact the CLI loads; a
    second run in the same checkout reloads it (its phases say so)."""
    from arrow_matrix_tpu.io import (
        as_levels,
        load_decomposition,
        load_level_widths,
        save_decomposition,
    )

    base = os.path.join(out_dir, f"ba_{n}_{BA_NEIGHBORS}_s{seed}")
    done = base + ".complete"
    if os.path.exists(done):
        with Phase("load saved decomposition", devices):
            loaded = load_decomposition(base, ARROW_WIDTH,
                                        block_diagonal=True)
            widths = load_level_widths(base, ARROW_WIDTH,
                                       block_diagonal=True)
            levels = as_levels(
                loaded, widths if widths is not None else ARROW_WIDTH)
    else:
        from arrow_matrix_tpu.decomposition import arrow_decomposition
        from arrow_matrix_tpu.utils.graphs import barabasi_albert

        with Phase("generate", devices):
            a = barabasi_albert(n, BA_NEIGHBORS, seed=seed)
        log(f"graph: n={n} nnz={a.nnz}")
        with Phase("decompose", devices):
            levels = arrow_decomposition(
                a, arrow_width=ARROW_WIDTH, max_levels=12,
                block_diagonal=True, seed=seed, backend="native")
        with Phase("save", devices):
            save_decomposition(levels, base, block_diagonal=True)
        with open(done, "w") as f:
            f.write(f"{len(levels)} levels\n")
    log(f"decomposition: {len(levels)} levels, nnz "
        f"{[int(lvl.matrix.nnz) for lvl in levels]}")
    return levels, base


def gate(levels, n: int, carriage: str = "f32") -> float:
    """The one-step golden gate of ``spmm_arrow --validate``."""
    from arrow_matrix_tpu.classes import BF16_TOLERANCE
    from arrow_matrix_tpu.utils import numerics

    tol = numerics.relative_tolerance(
        sum(int(lvl.matrix.nnz) for lvl in levels) / max(n, 1), iters=1)
    return max(tol, BF16_TOLERANCE) if carriage == "bf16" else tol


def run_cli(base: str, k: int, iterations: int, logdir: str, devices,
            extra=()) -> dict:
    """``spmm_arrow.main`` in-process; returns its segment summary."""
    from arrow_matrix_tpu.cli import spmm_arrow
    from arrow_matrix_tpu.utils import logging as wb

    argv = ["-f", base, "-w", str(ARROW_WIDTH), "--device", "tpu",
            "-c", str(k), "-z", str(iterations), "--validate",
            "--logdir", logdir, *extra]
    with Phase(f"spmm_arrow k={k}", devices) as ph:
        rc = spmm_arrow.main(argv)
    if rc != 0:
        fail(f"spmm_arrow.main({' '.join(argv)}) returned {rc}")
    s = wb.get_log().summarize()
    errs = s["frobenius_err"]
    log(f"spmm_arrow k={k}: build {s['build_time']['mean']:.3f} s, "
        f"first call (compile) {s['first_call_time']['mean']:.3f} s, "
        f"steady {s['spmm_time']['mean'] * 1e3:.3f} ms/iter (min "
        f"{s['spmm_time']['min'] * 1e3:.3f}) over "
        f"{s['spmm_time']['count']} iterations, max rel err "
        f"{errs['max']:.3e}, wall {ph.seconds:.3f} s")
    return s


def fused_phase(levels, n: int, seed: int, iterations: int, devices):
    """The fold operator through the XLA and the fused Pallas kernels,
    both against the scipy golden and against each other."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from arrow_matrix_tpu.decomposition import decomposition_matrix
    from arrow_matrix_tpu.obs import iteration_time_ms
    from arrow_matrix_tpu.parallel.multi_level import MultiLevelArrow
    from arrow_matrix_tpu.utils import numerics
    from arrow_matrix_tpu.utils.graphs import random_dense

    with Phase("golden operator", devices):
        golden = decomposition_matrix(levels)
    execs = {}
    for kernel in ("xla", "pallas_sell"):
        with Phase(f"build fold kernel={kernel}", devices):
            execs[kernel] = MultiLevelArrow(levels, ARROW_WIDTH, mesh=None,
                                            fmt="fold", kernel=kernel)
    # One fold layout for both kernels, so one upload serves all three
    # runs (bf16 carriage = the f32 carriage cast on the device).
    if not np.array_equal(execs["xla"].perm0, execs["pallas_sell"].perm0):
        fail("the XLA and pallas_sell fold builds disagree on row order")
    for k in FEATURES:
        x_host = random_dense(n, k, seed=seed + k)
        with Phase(f"golden k={k}", devices):
            want = golden @ x_host
        with Phase(f"upload k={k}", devices):
            x32 = jax.block_until_ready(execs["xla"].set_features(x_host))
        ref = None
        for kernel, carriage in (("xla", "f32"), ("pallas_sell", "f32"),
                                 ("pallas_sell", "bf16")):
            multi = execs[kernel]
            x = x32 if carriage == "f32" else x32.astype(jnp.bfloat16)
            tag = f"{kernel} {carriage} k={k}"
            # The executor's public pair step(x) == step_fn(x,
            # *step_operands()), compiled once ahead of time so the
            # first call splits into its parts and the HLO checked
            # below is the one that runs.
            ops = multi.step_operands()
            with Phase(f"trace+lower {tag}", devices):
                lowered = multi.step_fn.lower(x, *ops)
            with Phase(f"compile {tag}", devices):
                compiled = lowered.compile()

            def step(v, compiled=compiled, ops=ops):
                return compiled(v, *ops)

            with Phase(f"first call {tag}", devices):
                y = jax.block_until_ready(step(x))
            if kernel == "pallas_sell":
                hlo = compiled.as_text()
                if "tpu_custom_call" not in hlo:
                    fail(f"{tag}: compiled step holds no tpu_custom_call "
                         f"(kernel not compiled by Mosaic)")
                log(f"{tag}: compiled step holds "
                    f"{hlo.count('tpu_custom_call')} tpu_custom_call "
                    f"site(s)")
            got = multi.gather_result(y)
            err = numerics.relative_error(got, want)
            tol = gate(levels, n, carriage)
            ms = iteration_time_ms(step, x, iterations)
            line = (f"{tag}: steady {sum(ms) / len(ms):.3f} ms/iter (min "
                    f"{min(ms):.3f}), rel err vs golden {err:.3e} (gate "
                    f"{tol:.1e})")
            if ref is None:
                ref = got
            else:
                vs = numerics.relative_error(got, ref)
                line += f", vs XLA fold {vs:.3e}"
                if not np.isfinite(vs) or vs > tol:
                    fail(f"{tag}: differs from the XLA fold result by "
                         f"{vs:.3e} > {tol:.1e}")
            log(line)
            if not np.isfinite(err) or err > tol:
                fail(f"{tag}: rel err {err:.3e} over the gate {tol:.1e}")
            del x, y, compiled, step
        del x32


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: the single-chip phases; 4: only the mesh "
                         "path on all four chips of one host")
    ap.add_argument("--log2n", type=int, default=None,
                    help="graph size 2^N rows (default 22 on one chip, "
                         "21 with --chips 4; smaller only to find "
                         "faults quickly)")
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)

    here = os.path.dirname(os.path.abspath(__file__))
    if not os.path.isdir(os.path.join(here, "arrow_matrix_tpu")):
        fail(f"no arrow_matrix_tpu package next to {__file__}")
    sys.path.insert(0, here)
    from arrow_matrix_tpu.utils.platform import enable_compile_cache

    cache = enable_compile_cache()
    t_start = time.perf_counter()
    import jax

    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        fail(f"JAX found no TPU (platform {d0.platform!r}); this smoke "
             f"runs on the chip only")
    if len(devices) != args.chips:
        fail(f"--chips {args.chips} but JAX sees {len(devices)} "
             f"device(s)")
    jax.config.update("jax_default_matmul_precision", "highest")
    log(f"devices: {[(d.id, d.device_kind) for d in devices]}; "
        f"compile cache {cache}")

    n = 1 << (args.log2n or (22 if args.chips == 1 else 21))
    work = os.path.join(here, "bench_cache", "chip_smoke")
    os.makedirs(work, exist_ok=True)
    levels, base = build_workload(n, args.seed, work, devices)
    logdir = os.path.join(work, "logs")
    for k in FEATURES:
        extra = ("--mem_report",) if args.chips > 1 else ()
        run_cli(base, k, ITERATIONS, logdir, devices, extra)
        if args.chips > 1:
            peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                     for d in devices]
            if not max(peaks) or min(peaks) < max(peaks) // 8:
                fail(f"k={k}: the mesh run left devices nearly idle "
                     f"(peak bytes per device {peaks})")
    if args.chips == 1:
        fused_phase(levels, n, args.seed, ITERATIONS, devices)
    log(f"total {time.perf_counter() - t_start:.3f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
