"""One-process format sweep on the live chip at protocol scale.

Races the single-chip execution configs (fold, auto=ELL+platform heads,
and optionally dense/bf16 when they fit) over one cached decomposition,
printing ms/iter per config — the data that decides bench.py's default
format.  Run on the chip:

    python tools/chip_sweep.py [n]
"""

import os
import sys
import time

import numpy as np


def main() -> None:
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 1 << 20
    m, width, k, iters = 8, 2048, 16, 10

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import jax

    jax.config.update("jax_default_matmul_precision", "highest")
    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind}", flush=True)

    from bench import _cached_levels, _measure

    from arrow_matrix_tpu.decomposition.decompose import decomposition_spmm
    from arrow_matrix_tpu.parallel.multi_level import MultiLevelArrow
    from arrow_matrix_tpu.utils import numerics
    from arrow_matrix_tpu.utils.graphs import random_dense

    t0 = time.perf_counter()
    levels = _cached_levels(n, m, width, seed=7, max_levels=12)
    print(f"levels: {len(levels)} (setup {time.perf_counter() - t0:.1f}s)",
          flush=True)
    x_host = random_dense(n, k, seed=3)

    golden = decomposition_spmm(levels, x_host)
    nnz = sum(int(l.matrix.nnz) for l in levels)
    tol = numerics.relative_tolerance(nnz / max(n, 1), iters=1)

    configs = {
        "fold": dict(fmt="fold"),
        "auto": dict(fmt="auto"),
        "ell_headflat": dict(fmt="ell", head_fmt="flat"),
        "ell_headgell": dict(fmt="ell", head_fmt="gell"),
    }
    for name, kw in configs.items():
        try:
            t0 = time.perf_counter()
            multi = MultiLevelArrow(levels, width, mesh=None, **kw)
            build_s = time.perf_counter() - t0
            x = multi.set_features(x_host)
            ms = _measure(multi, x, iters)
            err = numerics.relative_error(
                multi.gather_result(multi.step(x)), golden)
            blk_gb = sum(b.device_nbytes()
                         for b in multi.blocks) / 2**30
            fmts = getattr(multi, "fmts", [])
            print(f"{name:14s} {ms:9.2f} ms/iter  err={err:.2e} "
                  f"(gate {tol:.0e})  blocks={blk_gb:.2f}GB "
                  f"build={build_s:.0f}s fmts={fmts}", flush=True)
            del multi, x
        except Exception as e:
            print(f"{name:14s} FAILED: {type(e).__name__}: {e}", flush=True)


if __name__ == "__main__":
    main()
