"""On-chip race: sell-layout degree ladder "default" vs "tight".

VERDICT r3 item 3: the tight ladder (growth 1.3, align 1) cuts LOGICAL
gather slots ~3.4x on block-diagonal levels by the host-side slot
model, but the win was never measured on a real chip.  This script
builds the feature-major SellMultiLevel (the mesh-path layout,
a2a routing) on a 1-device mesh over the REAL accelerator, measures
ms/iter for both ladders at protocol scale, validates each against the
host golden, and prints one JSON line the watcher archives as
``onchip_ladder_*.json``.

A 1-device mesh is the honest single-chip proxy: the ladder's effect
is per-device gather-iteration count, which doesn't need multiple
devices to measure (routing is identity at n_dev=1).  Reference
anchor: block padding policy, /root/reference/arrow/common/graphio.py
(394-399) — the reference pads blocks; we pad gather slots, and this
race decides how tightly.
"""

from __future__ import annotations

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main() -> None:
    # AMT_LADDER_CPU=1 runs the race logic on the host CPU (test
    # fixture; AMT_LADDER_N shrinks the scale); otherwise it runs
    # chip-or-bust.
    cpu_ok = os.environ.get("AMT_LADDER_CPU") == "1"
    if cpu_ok:
        from arrow_matrix_tpu.utils.platform import force_cpu_devices

        force_cpu_devices()
    from arrow_matrix_tpu.utils.platform import enable_compile_cache

    enable_compile_cache()
    import jax

    dev = jax.devices()[0]
    platform, kind = dev.platform, dev.device_kind
    out: dict = {"metric": "ladder_race", "platform": platform,
                 "device_kind": kind}
    if not cpu_ok and platform != "tpu":
        out["error"] = f"no TPU (platform {platform})"
        print(json.dumps(out), flush=True)
        raise SystemExit(1)

    jax.config.update("jax_default_matmul_precision", "highest")

    import numpy as np

    import bench  # repo-root bench: shared cached decomposition

    from arrow_matrix_tpu.decomposition.decompose import decomposition_spmm
    from arrow_matrix_tpu.parallel.mesh import make_mesh
    from arrow_matrix_tpu.parallel.sell_slim import SellMultiLevel
    from arrow_matrix_tpu.utils import numerics
    from arrow_matrix_tpu.utils.graphs import random_dense

    n = int(os.environ.get("AMT_LADDER_N", 1 << 20))
    m, width, k, iters = 8, 2048, 16, 10
    if n < (1 << 18):
        width, iters = 512, 5   # test-fixture scale
    os.chdir(REPO)
    levels = bench._cached_levels(n, m, width, seed=7, max_levels=12)
    nnz = sum(int(l.matrix.nnz) for l in levels)
    tol = numerics.relative_tolerance(nnz / n, iters=1)
    x_host = random_dense(n, k, seed=3)
    want = decomposition_spmm(levels, x_host)
    mesh = make_mesh((1,), ("blocks",))
    out.update({"n": n, "width": width, "k": k, "iters": iters,
                "gate": tol, "runs": {}})

    def measure(obj, x) -> float:
        def chain(cnt):
            t0 = time.perf_counter()
            xd = obj.run(x, cnt) if cnt else x
            np.asarray(jax.device_get(xd)).ravel()[0]
            return time.perf_counter() - t0

        chain(iters)  # compile + warm
        rtt = min(chain(0) for _ in range(3))
        return max((chain(iters) - rtt) / iters, 1e-9) * 1e3

    for name in ("default", "tight"):
        t0 = time.perf_counter()
        try:
            sm = SellMultiLevel(levels, width, mesh, routing="a2a",
                                ladder=name)
            build_s = time.perf_counter() - t0
            x = sm.set_features(x_host)
            ms = measure(sm, x)
            err_rel = numerics.relative_error(
                sm.gather_result(sm.step(x)), want)
            # Logical gather slots: every (tier-row, slot) pair the
            # gather kernels iterate — the ladder's cost model.
            slots = 0
            for op in sm.ops:
                for stack in (op.body, op.head):
                    slots += sum(int(np.prod(c.shape))
                                 for c in stack.cols)
            out["runs"][name] = {
                "ms": round(ms, 3), "err": err_rel,
                "build_s": round(build_s, 1),
                "gated": bool(np.isfinite(err_rel) and err_rel <= tol),
            }
            if slots:
                out["runs"][name]["gather_slots"] = slots
            print(f"[ladder_race] {name}: {ms:.1f} ms/iter "
                  f"err={err_rel:.2e}", file=sys.stderr, flush=True)
            del sm, x
        except Exception as e:
            out["runs"][name] = {
                "error": f"{type(e).__name__}: {str(e)[:300]}"}
    gated = {nm: r["ms"] for nm, r in out["runs"].items()
             if r.get("gated")}
    if gated:
        out["winner"] = min(gated, key=gated.get)
        out["value"] = gated[out["winner"]]
        out["unit"] = "ms"
    print(json.dumps(out), flush=True)
    if not gated:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
