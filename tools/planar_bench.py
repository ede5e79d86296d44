"""Planar-class on-chip headline (VERDICT r3 item 6).

The reference paper's communication bound targets planar /
minor-excluded graphs (reference README.md:3: "polynomial reduction in
communication volume ... for planar graphs"); the framework's banded
fast path (decompose.py band_detect) decomposes a 2-D grid to ONE
level — zero inter-level routing by construction.  This script

1. decomposes a scrambled ``side x side`` grid through the banded/RCM
   fast path (cached),
2. runs the fold iteration on the REAL chip, golden-gated,
3. reports the communication story from an 8-device virtual-CPU
   subprocess: per-iteration collective bytes of the sell/a2a layout
   on the grid (the halo-only exchange; inter-level volume is
   structurally zero at K=1).

Output: one JSON line the watcher archives as ``onchip_planar_*.json``.
AMT_PLANAR_CPU=1 runs the iteration on the host CPU at a reduced side
(test fixture).  AMT_PLANAR_SIDE overrides the grid side.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

_COMM_CHILD = r"""
import json, sys
sys.path.insert(0, %(repo)r)
from arrow_matrix_tpu.utils.platform import force_cpu_devices
force_cpu_devices(8)
import numpy as np
from arrow_matrix_tpu.decomposition import arrow_decomposition
from arrow_matrix_tpu.parallel.mesh import make_mesh
from arrow_matrix_tpu.parallel.sell_slim import SellMultiLevel
from arrow_matrix_tpu.utils import commstats
from arrow_matrix_tpu.utils.graphs import grid_graph, random_dense

side = %(side)d
rng = np.random.default_rng(3)
scramble = rng.permutation(side * side)
g = grid_graph(side)[scramble][:, scramble].tocsr()
levels = arrow_decomposition(g, arrow_width=%(width)d, max_levels=10,
                             block_diagonal=True, seed=7)
sm = SellMultiLevel(levels, %(width)d, make_mesh((8,), ("blocks",)),
                    routing="a2a")
xt = sm.set_features(random_dense(side * side, 16, seed=3))
stats = commstats.collective_stats(sm.step_fn, xt, *sm.step_operands())
print(json.dumps({
    "levels": len(levels),
    "hops": [int(op.hops) for op in sm.ops],
    "halo_rem_rows": [int(op.rem) for op in sm.ops],
    "collective_bytes_per_iter": int(stats["total_bytes"]),
    "collective_count": int(sum(v["count"] for kk, v in stats.items()
                                if isinstance(v, dict))),
}))
"""


def main() -> None:
    cpu = os.environ.get("AMT_PLANAR_CPU") == "1"
    if cpu:
        from arrow_matrix_tpu.utils.platform import force_cpu_devices

        force_cpu_devices()
    from arrow_matrix_tpu.utils.platform import enable_compile_cache

    enable_compile_cache()
    import jax

    dev = jax.devices()[0]
    platform, kind = dev.platform, dev.device_kind
    out: dict = {"metric": "planar_grid_iter_ms",
                 "platform": platform, "device_kind": kind}
    if not cpu and platform != "tpu":
        out["error"] = f"no TPU (platform {platform})"
        print(json.dumps(out), flush=True)
        raise SystemExit(1)

    side = int(os.environ.get("AMT_PLANAR_SIDE",
                              256 if cpu else 4096))
    # bf16 feature carriage halves the resident feature bytes — the
    # knob that fits the 10240^2 (10^8-row) grid on one 16 GB v5e
    # (operator ~1.7 GB + bf16 features ~6.7 GB).  f32 accumulation
    # throughout (ops/ell.py), so the one-step golden still gates,
    # against the documented bf16 carriage tolerance.
    feat_dtype = os.environ.get("AMT_PLANAR_DTYPE") or None
    if feat_dtype not in (None, "bf16"):
        raise SystemExit(f"AMT_PLANAR_DTYPE must be bf16 or unset, "
                         f"got {feat_dtype}")
    # The one-level fast path needs width >= the grid's RCM bandwidth
    # (~side); 1.25x matches the scale-ladder's 8192^2 rung (width
    # 10240).  THIS is the planar story: width covers the band, K=1,
    # zero inter-level routing.
    width = max(side * 5 // 4, 64)
    n = side * side
    out.update({"side": side, "n": n, "width": width, "k": 16})

    jax.config.update("jax_default_matmul_precision", "highest")

    import numpy as np

    from arrow_matrix_tpu.decomposition import arrow_decomposition
    from arrow_matrix_tpu.decomposition.decompose import decomposition_spmm
    from arrow_matrix_tpu.parallel.multi_level import MultiLevelArrow
    from arrow_matrix_tpu.utils import numerics
    from arrow_matrix_tpu.utils.graphs import grid_graph, random_dense

    # Scrambled grid: band_detect must RECOVER the band via RCM — the
    # honest planar case (a pre-ordered grid would trivially pass).
    t0 = time.perf_counter()
    rng = np.random.default_rng(3)
    scramble = rng.permutation(n)
    g = grid_graph(side)[scramble][:, scramble].tocsr()
    out["build_graph_s"] = round(time.perf_counter() - t0, 1)
    t0 = time.perf_counter()
    levels = arrow_decomposition(g, arrow_width=width, max_levels=10,
                                 block_diagonal=True, seed=7)
    out["decompose_s"] = round(time.perf_counter() - t0, 1)
    out["levels"] = len(levels)
    nnz = sum(int(l.matrix.nnz) for l in levels)
    out["nnz"] = nnz

    iters = 5 if cpu else 10
    x_host = random_dense(n, 16, seed=3)
    # bf16 carriage rounds the carried features once per step: the
    # documented tolerance is ~2e-2 relative (bf16 has ~3 decimal
    # digits; accumulation stays f32) vs the f32 gate formula.
    tol = (2e-2 if feat_dtype == "bf16"
           else numerics.relative_tolerance(nnz / n, iters=1))
    out["feature_dtype"] = feat_dtype or "f32"
    want = decomposition_spmm(levels, x_host)
    out["runs"] = {}
    # fold vs fold_tight: a degree-4 grid pads 2.0x under the default
    # align-8 slots and ~1.0x under tight packing — the planar case is
    # where tight packing's slot cut is LARGEST (cf. the BA-8 race
    # where it is -17%).
    for name, kwargs in (("fold", dict(fmt="fold")),
                         ("fold_tight", dict(fmt="fold",
                                             fold_growth=1.1,
                                             fold_align=1))):
        if feat_dtype == "bf16" and name == "fold":
            # The 10^8 bf16 config exists to FIT one chip: two
            # resident builds would not (and fold_tight is the known
            # slot winner on grids — 1.0x vs 2.0x nnz).
            continue
        t0 = time.perf_counter()
        multi = MultiLevelArrow(levels, width, mesh=None,
                                feature_dtype=feat_dtype, **kwargs)
        r = {"build_s": round(time.perf_counter() - t0, 1)}
        x = multi.set_features(x_host)

        def chain(cnt):
            t0 = time.perf_counter()
            xd = multi.run(x, cnt) if cnt else x
            np.asarray(jax.device_get(xd)).ravel()[0]
            return time.perf_counter() - t0

        chain(iters)   # compile + warm
        rtt = min(chain(0) for _ in range(3))
        ms = max((chain(iters) - rtt) / iters, 1e-9) * 1e3
        err = numerics.relative_error(
            multi.gather_result(multi.step(x)), want)
        r.update({"ms": round(ms, 3), "err": err,
                  "gated": bool(np.isfinite(err) and err <= tol)})
        slots = sum(int(b.n_slots) for b in multi.blocks
                    if hasattr(b, "n_slots"))
        if slots:
            r.update({"gather_slots": slots,
                      "slots_per_s": round(slots / (ms * 1e-3)),
                      "slots_over_nnz": round(slots / max(nnz, 1), 3)})
        out["runs"][name] = r
        del multi, x
    gated = {nm: r["ms"] for nm, r in out["runs"].items()
             if r.get("gated")}
    out["gate"] = tol
    if gated:
        winner = min(gated, key=gated.get)
        out.update({"winner": winner, "value": gated[winner],
                    "unit": "ms",
                    "err": out["runs"][winner]["err"], "gated": True})
    else:
        out["gated"] = False

    # Communication story (virtual 8-dev mesh, separate CPU process —
    # this process owns the accelerator).  Small fixed side: the comm
    # STRUCTURE (1 level, halo-only) is side-independent; bytes scale
    # linearly and the grid at full side would cost minutes of host
    # build for the same story.
    try:
        child = subprocess.run(
            [sys.executable, "-c",
             _COMM_CHILD % {"repo": REPO, "side": min(side, 256),
                            "width": max(min(side, 256) * 5 // 4, 64)}],
            capture_output=True, text=True, timeout=600,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        if child.returncode == 0 and child.stdout.strip():
            out["comm_8dev"] = json.loads(
                child.stdout.strip().splitlines()[-1])
        else:
            out["comm_error"] = child.stderr.strip()[-300:]
    except Exception as e:
        out["comm_error"] = f"{type(e).__name__}: {str(e)[:200]}"

    print(json.dumps(out), flush=True)
    if not out.get("gated"):
        raise SystemExit(1)


if __name__ == "__main__":
    main()
