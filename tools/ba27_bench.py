"""On-chip single-chip iterate at the BA-2^27 scale point (134.2M
rows / 1.07e9 nnz — the reference's "hundreds of millions of rows"
headline class, reference README.md:3) from the packed operator
exported by the ``rehearse_1e8_ba_step`` scale-ladder rung.

The offline half (generate 2^27 -> native decompose -> fold ->
export, ~2.2 h of host work) runs once on the host; this tool is the
online half, run on the chip: memmap-load the packed SELL tiers,
upload (~4.5 GB operator), bf16 feature
carriage (2 x 4.3 GB), donated scan — the measured HBM budget is in
the rung's ``rehearsal.json`` (~14 GB vs 16 GB v5e, which is why the
export uses the tight packing).

Prints ONE JSON line; nonzero exit when the chip is unreachable or
the export is missing/toy-sized.

Usage: python tools/ba27_bench.py
"""

from __future__ import annotations

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

EXPORT = os.environ.get(
    "AMT_BA27_EXPORT", os.path.join(REPO, "bench_cache", "ba27_fold"))


def main() -> None:
    meta_path = os.path.join(EXPORT, "meta.json")
    reh_path = os.path.join(EXPORT, "rehearsal.json")
    if not (os.path.exists(meta_path) and os.path.exists(reh_path)):
        print(json.dumps({"stage": "ba27", "error": "no export"}))
        raise SystemExit(2)
    with open(reh_path) as f:
        reh = json.load(f)
    if reh["n"] < (1 << 27) and not os.environ.get("AMT_BA27_ALLOW_SMALL"):
        print(json.dumps({"stage": "ba27", "error":
                          f"export is a logic-test toy (n={reh['n']})"}))
        raise SystemExit(2)

    if os.environ.get("AMT_BA27_FORCE_CPU"):
        # Logic-validation mode (tests): run the identical path on the
        # host backend instead of probing for an accelerator.
        from arrow_matrix_tpu.utils.platform import force_cpu_devices

        force_cpu_devices()
        platform, kind = "cpu(forced)", "host"
    else:
        import jax

        dev = jax.devices()[0]
        platform, kind = dev.platform, dev.device_kind
        if platform != "tpu":
            print(json.dumps({"stage": "ba27", "error":
                              f"no TPU (platform {platform})"}))
            raise SystemExit(3)

    import numpy as np

    from arrow_matrix_tpu.parallel.multi_level import MultiLevelArrow
    from arrow_matrix_tpu.utils.graphs import random_dense

    out = {"stage": "ba27", "platform": platform, "device_kind": kind,
           "n": reh["n"], "k": reh["k"], "feature_dtype": "bf16",
           "hbm_budget": reh.get("hbm_budget")}
    t0 = time.perf_counter()
    ml = MultiLevelArrow.load_folded(EXPORT, gather_budget=1 << 29)
    out["load_upload_s"] = round(time.perf_counter() - t0, 1)

    x = random_dense(reh["n"], reh["k"], seed=reh["x_seed"])
    t0 = time.perf_counter()
    xt = ml.set_features(x)
    del x
    out["set_features_s"] = round(time.perf_counter() - t0, 1)

    # One donated step, golden-gated against the rehearsal's scipy
    # sample (the offline run saved want = a[rows] @ x).
    rows = np.load(os.path.join(EXPORT, "sample_rows.npy"))
    want = np.load(os.path.join(EXPORT, "sample_out.npy"))
    t0 = time.perf_counter()
    y = ml.run(xt, 1, donate=True)
    got = np.asarray(y[:, ml.inv_perm0[rows]], dtype=np.float32).T
    out["first_step_s_inc_compile"] = round(time.perf_counter() - t0, 1)
    rel = float(np.linalg.norm(got - want) / np.linalg.norm(want))
    out["golden_sample_rel_err"] = round(rel, 6)
    if rel >= 2e-2:
        out["error"] = "golden gate failed"
        print(json.dumps(out))
        raise SystemExit(4)

    # Timed iterate: one scan dispatch, one small host fetch at the
    # end.  The first
    # length-iters donated run compiles that scan program (static n
    # differs from the n=1 golden step) — warm it, then time the
    # second invocation of the SAME compiled program.
    iters = int(os.environ.get("AMT_BA27_ITERS", 8))
    t0 = time.perf_counter()
    y = ml.run(y, iters, donate=True)
    _ = np.asarray(y[:, :128])
    out["warm_run_s_inc_compile"] = round(time.perf_counter() - t0, 1)
    t0 = time.perf_counter()
    y = ml.run(y, iters, donate=True)
    _ = np.asarray(y[:, :128])
    dt = time.perf_counter() - t0
    out["iters"] = iters
    out["ms_per_iter"] = round(dt / iters * 1000, 1)
    out["slots"] = int(ml.blocks[0].n_slots)
    out["slot_rate_g_per_s"] = round(
        ml.blocks[0].n_slots * iters / dt / 1e9, 2)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
