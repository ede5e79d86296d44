"""Scale ladder toward the reference's "hundreds of millions of rows"
claim (VERDICT r2 item 3; reference README.md:3).

Rungs, each in its own subprocess so peak host RSS (ru_maxrss) is
attributable per phase:

  decompose24      BA-8 n=2^24 (16.7M rows, ~268M nnz) full native
                   decomposition -> artifact on disk (cached; the
                   offline/online split).
  ingest24         memmapped artifact -> SellMultiLevel on an 8-device
                   virtual CPU mesh via the STREAMING builder
                   (materialize=False): build seconds, peak RSS (must
                   stay far below the ~6.4 GB the in-memory levels
                   would hold), 2 iterations ms/iter, column-sliced
                   golden gate on one step.
  decompose26_grid planar 8192^2 grid (67M rows) decompose-only
                   through the banded fast path (the paper's
                   minor-excluded class): seconds + RSS; must return
                   ONE level.
  backend_race22   BA-8 n=2^22 full decomposition, native vs numpy
                   backend, same flags: the native decomposer's
                   raison d'etre measured at >=1e7-nnz scale.

Results append to bench_results/scale_ladder.json.  Everything is
host-side (decomposition + streaming ingest are the host's job); the
on-chip iterate at this scale is tools/ba27_bench.py's job.

Usage: PYTHONPATH=/root/repo python tools/scale_ladder.py [rung ...]
"""

from __future__ import annotations

import json
import os
import resource
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE = os.path.join(REPO, "bench_cache")
OUT = os.path.join(REPO, "bench_results", "scale_ladder.json")
N24, N22 = 1 << 24, 1 << 22
WIDTH = 2048


def _rss_gb() -> float:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20


def _artifact24() -> str:
    return os.path.join(CACHE, f"ba_{N24}_8_w{WIDTH}_s7_L14")


def rung_decompose24() -> dict:
    from arrow_matrix_tpu.decomposition.decompose import arrow_decomposition
    from arrow_matrix_tpu.io import save_decomposition
    from arrow_matrix_tpu.utils.graphs import barabasi_albert

    base = _artifact24()
    cached = os.path.exists(base + ".complete")
    if cached and os.environ.get("AMT_LADDER_FORCE") != "1":
        return {"cached": True, "base": base}
    t0 = time.perf_counter()
    a = barabasi_albert(N24, 8, seed=7)
    gen_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    levels = arrow_decomposition(a, arrow_width=WIDTH, max_levels=14,
                                 block_diagonal=True, seed=7,
                                 backend="native")
    dec_s = time.perf_counter() - t0
    del a
    if not cached:
        # AMT_LADDER_FORCE re-MEASURES decompose (the native-kernel
        # speedup rung) without re-writing the multi-GB artifact.
        save_decomposition(levels, base, block_diagonal=True)
        with open(base + ".complete", "w") as f:
            f.write(f"{len(levels)} levels\n")
    return {"n": N24, "nnz": sum(int(l.matrix.nnz) for l in levels),
            "levels": len(levels), "generate_s": round(gen_s, 1),
            "decompose_s": round(dec_s, 1), "peak_rss_gb": round(_rss_gb(), 2),
            "backend": "native"}


def rung_ingest24() -> dict:
    from arrow_matrix_tpu.utils.platform import force_cpu_devices

    force_cpu_devices(8)
    import jax

    from arrow_matrix_tpu.io import (
        as_levels,
        load_decomposition,
        load_level_widths,
    )
    from arrow_matrix_tpu.parallel.mesh import make_mesh
    from arrow_matrix_tpu.parallel.sell_slim import SellMultiLevel
    from arrow_matrix_tpu.utils import numerics
    from arrow_matrix_tpu.utils.graphs import random_dense

    base = _artifact24()
    t0 = time.perf_counter()
    loaded = load_decomposition(base, WIDTH, block_diagonal=True,
                                mem_map=True)
    widths = load_level_widths(base, WIDTH, block_diagonal=True)
    if widths is None:
        widths = WIDTH
    levels = as_levels(loaded, widths, materialize=False)
    load_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    sm = SellMultiLevel(levels, WIDTH, make_mesh((8,), ("blocks",)),
                        routing="a2a")
    build_s = time.perf_counter() - t0
    build_rss = _rss_gb()

    k = 16
    x = random_dense(N24, k, seed=3)
    t0 = time.perf_counter()
    xt = sm.set_features(x)
    got = sm.gather_result(sm.step(xt))
    step1_s = time.perf_counter() - t0

    # Column-sliced golden (SpMM is column-separable): one host pass
    # over the memmapped levels at 4 columns gates the whole step.
    # (Each level's CSR materializes transiently here, so the
    # golden's RSS is excluded from the streaming-build claim —
    # build_peak_rss_gb above is captured before this block.)
    t0 = time.perf_counter()
    nnz = 0
    import numpy as np
    from scipy import sparse as sp

    x4 = np.ascontiguousarray(x[:, :4])
    want = np.zeros((N24, 4), np.float32)
    for lvl in levels:
        d, i, p = lvl.matrix
        nz = int(np.asarray(p[-1]))
        m = sp.csr_matrix(
            ((np.ones(nz, np.float32) if d is None
              else np.asarray(d[:nz], np.float32)),
             np.asarray(i[:nz]), np.asarray(p)),
            shape=(N24, N24))
        partial = m @ x4[lvl.permutation]
        want += partial[lvl.inverse_permutation]
        nnz += nz
        del m
    golden_s = time.perf_counter() - t0
    err = numerics.relative_error(got[:, :4], want)
    tol = numerics.relative_tolerance(nnz / N24)
    if not err <= tol:
        raise RuntimeError(f"2^24 streamed step misses golden: "
                           f"{err:.3e} > {tol:.3e}")

    # ms/iter, host CPU backend (the chip path is the heal pipeline's).
    t0 = time.perf_counter()
    xt2 = sm.run(xt, 2)
    jax.block_until_ready(xt2)
    iter_ms = (time.perf_counter() - t0) / 2 * 1e3
    return {"load_s": round(load_s, 1), "build_s": round(build_s, 1),
            "build_peak_rss_gb": round(build_rss, 2),
            "first_step_s": round(step1_s, 1),
            "iter_ms_cpu": round(iter_ms, 1),
            "golden_err": err, "golden_gate": tol,
            "golden_s": round(golden_s, 1),
            "device_bytes_gb": round(sum(
                o.device_nbytes() for o in sm.ops) / 2**30, 2),
            "peak_rss_gb": round(_rss_gb(), 2)}


def rung_decompose26_grid() -> dict:
    from arrow_matrix_tpu.decomposition.decompose import arrow_decomposition
    from arrow_matrix_tpu.utils.graphs import grid_graph

    side = 8192
    # A grid's RCM bandwidth is ~side, so the banded fast path needs
    # arrow_width >= side; 10240 matches the reference's own example
    # width scale (README.md:72 uses 10000).  At width 2048 the gate
    # correctly refuses and the recursion produces 2 levels instead
    # (measured 428.8 s) — the fast path must be driven at a width
    # the graph class actually fits.
    width = 10240
    t0 = time.perf_counter()
    a = grid_graph(side)
    gen_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    levels = arrow_decomposition(a, arrow_width=width, max_levels=14,
                                 block_diagonal=False, seed=7,
                                 backend="native")
    dec_s = time.perf_counter() - t0
    return {"n": side * side, "nnz": int(a.nnz), "width": width,
            "levels": len(levels),
            "one_level_fast_path": len(levels) == 1,
            "generate_s": round(gen_s, 1), "decompose_s": round(dec_s, 1),
            "peak_rss_gb": round(_rss_gb(), 2)}


def rung_decompose_1e8_grid() -> dict:
    """The reference's headline scale claim is "hundreds of millions
    of rows" (reference README.md:3).  A 10240^2 grid is 104.9M rows /
    ~419M nnz — the planar/minor-excluded class the paper's bound
    targets — decomposed through the banded RCM fast path to ONE
    level.  Scrambled first: the fast path must RECOVER the band, not
    inherit it from a convenient input order."""
    import numpy as np

    from arrow_matrix_tpu.decomposition.decompose import arrow_decomposition
    from arrow_matrix_tpu.utils.graphs import grid_graph

    side = 10240
    width = 12800           # >= RCM bandwidth (~side), same 1.25x rule
    t0 = time.perf_counter()
    a = grid_graph(side)
    rng = np.random.default_rng(3)
    scramble = rng.permutation(side * side)
    a = a[scramble][:, scramble].tocsr()
    gen_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    levels = arrow_decomposition(a, arrow_width=width, max_levels=14,
                                 block_diagonal=False, seed=7,
                                 backend="native")
    dec_s = time.perf_counter() - t0
    return {"n": side * side, "nnz": int(a.nnz), "width": width,
            "levels": len(levels),
            "one_level_fast_path": len(levels) == 1,
            "scrambled_input": True,
            "generate_s": round(gen_s, 1), "decompose_s": round(dec_s, 1),
            "peak_rss_gb": round(_rss_gb(), 2)}


def rung_decompose_1e8_ba() -> dict:
    """Power-law at the reference's headline scale: BA m=4 at n=2^27 =
    134.2M rows / ~1.07e9 nnz, full native recursion (the HARD class —
    no banded shortcut).  Decompose-only: the on-chip iterate at this
    scale exceeds one v5e's HBM at k=16 f32 (operator ~4.3 GB + two
    ~8.6 GB feature buffers); bf16 carriage or k-tiling would fit it,
    which is multi-chip territory by design."""
    from arrow_matrix_tpu.decomposition.decompose import arrow_decomposition
    from arrow_matrix_tpu.utils.graphs import barabasi_albert

    n = 1 << 27
    t0 = time.perf_counter()
    a = barabasi_albert(n, 4, seed=7)
    gen_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    levels = arrow_decomposition(a, arrow_width=WIDTH, max_levels=14,
                                 block_diagonal=True, seed=7,
                                 backend="native")
    dec_s = time.perf_counter() - t0
    return {"n": n, "nnz": sum(int(l.matrix.nnz) for l in levels),
            "levels": len(levels), "generate_s": round(gen_s, 1),
            "decompose_s": round(dec_s, 1),
            "peak_rss_gb": round(_rss_gb(), 2), "backend": "native"}


def rung_rehearse_1e8_ba_step() -> dict:
    """BA-2^27 single-chip STEP rehearsal, end-to-end in degraded
    (host CPU) mode — VERDICT r4 item 2.  Generate -> native decompose
    -> fold into ONE bf16-carriage SELL operator -> export the packed
    operator (offline/online split: tools/ba27_bench.py ingests the
    export on the chip and steps without redoing the ~2.2 h of host
    work) -> explicit HBM budget vs one 16 GB v5e -> ONE donated
    run() step golden-gated against scipy on sampled rows.

    Feasibility argument made concrete: at n=2^27, k=16 the f32
    carriage needs 2 x 8.6 GB buffers + ~5 GB operator (over 16 GB);
    bf16 carriage (2 x 4.3 GB) + scan-buffer donation (input aliased
    to the carry, so ONE carried buffer + the in-flight output) fits.
    """
    from arrow_matrix_tpu.utils.platform import force_cpu_devices

    force_cpu_devices()
    import numpy as np

    from arrow_matrix_tpu.decomposition.decompose import arrow_decomposition
    from arrow_matrix_tpu.parallel.multi_level import MultiLevelArrow
    from arrow_matrix_tpu.utils.graphs import barabasi_albert, random_dense

    # AMT_BA27_LOGN: logic-validation knob (tests run the identical
    # path at a small n; the recorded rung always runs the real 2^27).
    n = 1 << int(os.environ.get("AMT_BA27_LOGN", 27))
    k, x_seed = 16, 5
    out: dict = {"n": n, "k": k, "feature_dtype": "bf16"}
    t0 = time.perf_counter()
    a = barabasi_albert(n, 4, seed=7)
    out["generate_s"] = round(time.perf_counter() - t0, 1)
    t0 = time.perf_counter()
    levels = arrow_decomposition(a, arrow_width=WIDTH, max_levels=14,
                                 block_diagonal=True, seed=7,
                                 backend="native")
    out["decompose_s"] = round(time.perf_counter() - t0, 1)
    out["levels"] = len(levels)
    out["nnz"] = sum(int(lvl.matrix.nnz) for lvl in levels)
    t0 = time.perf_counter()
    # Tight packing (the fold_tight candidate): ~1.04x nnz logical
    # slots vs ~1.25x at the stacked default — at 2^27 that is the
    # difference between a ~5.4 GB and a ~4.5 GB operator, which the
    # 16 GB budget below needs.  dense_budget pins gather_budget to
    # 512 MB (2^31 // 4) so the scratch term is explicit, not
    # device-derived.
    ml = MultiLevelArrow(levels, WIDTH, mesh=None, fmt="fold",
                         feature_dtype="bf16", fold_growth=1.1,
                         fold_align=1, dense_budget=1 << 31)
    del levels
    out["fold_build_s"] = round(time.perf_counter() - t0, 1)
    # Write the export to a temp dir and swap it in at the END
    # (ba27_bench gates on rehearsal.json — it must never see a
    # half-written operator).  AMT_BA27_EXPORT: same
    # override the consumer (tools/ba27_bench.py) honors — tests
    # point both at a scratch dir and never touch the live path.
    export_dir = os.environ.get("AMT_BA27_EXPORT",
                                os.path.join(CACHE, "ba27_fold"))
    tmp_dir = export_dir + ".tmp"
    import shutil

    shutil.rmtree(tmp_dir, ignore_errors=True)
    t0 = time.perf_counter()
    ml.export_folded(tmp_dir)
    out["export_s"] = round(time.perf_counter() - t0, 1)

    # HBM budget: what the REAL chip must hold.  Operator = int32 slot
    # tiles + per-tier degree vectors (binary adjacency: no data
    # array); carriage = ONE resident bf16 buffer thanks to donation,
    # plus the in-flight output; scratch = the auto-chunk gather bound.
    sell = ml.blocks[0]
    total = ml.total_rows
    cols_gb = sum(c.shape[0] * c.shape[1] * 4 for c in sell.cols) / 2**30
    deg_gb = sum(d.shape[0] * 4 for d in (sell.deg or ())) / 2**30
    buf_gb = k * total * 2 / 2**30          # bf16 carriage
    scratch_gb = ((1 << 31) // 4) / 2**30   # the pinned gather budget
    budget = {
        "operator_cols_gb": round(cols_gb, 2),
        "operator_deg_gb": round(deg_gb, 2),
        "carried_buffer_bf16_gb": round(buf_gb, 2),
        "in_flight_output_gb": round(buf_gb, 2),
        "gather_scratch_gb": round(scratch_gb, 2),
        "total_gb": round(cols_gb + deg_gb + 2 * buf_gb + scratch_gb, 2),
        "hbm_gb": 16.0,
    }
    budget["fits"] = budget["total_gb"] < budget["hbm_gb"]
    out["hbm_budget"] = budget
    print(f"[ba27] HBM budget: {json.dumps(budget)}", file=sys.stderr,
          flush=True)
    assert budget["fits"], "2^27 bf16 single-chip budget exceeded"

    x = random_dense(n, k, seed=x_seed)
    t0 = time.perf_counter()
    xt = ml.set_features(x)
    out["set_features_s"] = round(time.perf_counter() - t0, 1)
    t0 = time.perf_counter()
    y = np.asarray(ml.run(xt, 1, donate=True))
    out["host_step_s_inc_compile"] = round(time.perf_counter() - t0, 1)

    # Golden gate: scipy on sampled rows (the full 134M-row golden
    # would double peak RSS for no extra signal).
    rows = np.linspace(0, n - 1, 4096).astype(np.int64)
    res = y[:, ml.inv_perm0[rows]].astype(np.float32).T   # (4096, k)
    want = a[rows] @ x
    rel = float(np.linalg.norm(res - want) / np.linalg.norm(want))
    out["golden_sample_rel_err"] = round(rel, 6)
    assert rel < 2e-2, f"sampled golden off: {rel}"
    np.save(os.path.join(tmp_dir, "sample_rows.npy"), rows)
    np.save(os.path.join(tmp_dir, "sample_out.npy"),
            want.astype(np.float32))
    with open(os.path.join(tmp_dir, "rehearsal.json"), "w") as f:
        json.dump({**out, "x_seed": x_seed}, f, indent=1)
    shutil.rmtree(export_dir, ignore_errors=True)
    os.rename(tmp_dir, export_dir)
    out["peak_rss_gb"] = round(_rss_gb(), 2)
    out["export_dir"] = export_dir
    return out


def _backend_race(n: int) -> dict:
    from arrow_matrix_tpu.decomposition.decompose import arrow_decomposition
    from arrow_matrix_tpu.utils.graphs import barabasi_albert

    a = barabasi_albert(n, 8, seed=7)
    out = {"n": n, "nnz": int(a.nnz)}
    for backend in ("native", "numpy"):
        t0 = time.perf_counter()
        levels = arrow_decomposition(a, arrow_width=WIDTH, max_levels=14,
                                     block_diagonal=True, seed=7,
                                     backend=backend)
        out[backend + "_s"] = round(time.perf_counter() - t0, 1)
        out[backend + "_levels"] = len(levels)
    out["speedup"] = round(out["numpy_s"] / out["native_s"], 2)
    return out


def rung_dryrun_multichip_mid() -> dict:
    """Opt-in mid-scale multichip dry run (VERDICT r4 item 7): n=2^16
    BA-8 at width 512 on an 8-device virtual CPU mesh, fold + sell-a2a,
    each golden-gated, with a trace-time comm account per algorithm
    carrying the graft-stream ``exposed_comm_ms`` model — so MULTICHIP
    artifacts record more than toy-shape evidence."""
    import __graft_entry__ as ge

    return ge.dryrun_multichip(8, scale="mid")


def rung_dryrun_repl_sweep() -> dict:
    """2.5D replication sweep (graft-repl): fixed-B sell-a2a at
    c in {1,2,4} on an 8-device virtual CPU mesh, enforcing the honest
    contract — bit-identical results at every c and measured wire
    bytes exactly 1/c — plus the 8-device c=1 production reference.
    The rung FAILS (non-zero exit) if either invariant breaks; the
    committed record is the evidence PERFORMANCE.md's 2.5D section
    cites."""
    import __graft_entry__ as ge

    return ge.dryrun_multichip(8, scale="repl")


def rung_backend_race22() -> dict:
    return _backend_race(N22)


def rung_backend_race23() -> dict:
    return _backend_race(1 << 23)


RUNGS = {"decompose24": rung_decompose24, "ingest24": rung_ingest24,
         "decompose26_grid": rung_decompose26_grid,
         "decompose_1e8_grid": rung_decompose_1e8_grid,
         "decompose_1e8_ba": rung_decompose_1e8_ba,
         "rehearse_1e8_ba_step": rung_rehearse_1e8_ba_step,
         "dryrun_multichip_mid": rung_dryrun_multichip_mid,
         "dryrun_repl_sweep": rung_dryrun_repl_sweep,
         "backend_race22": rung_backend_race22,
         "backend_race23": rung_backend_race23}

#: What a bare `python tools/scale_ladder.py` runs.  The 1e8 rungs are
#: opt-in by explicit name: the BA 2^27 decompose needs hour-plus wall
#: clock and tens of GB of RSS — a no-arg ladder run must stay bounded.
#: The mid-scale multichip dry run and the 2.5D repl sweep are opt-in
#: too: they are evidence gathering, not part of the bounded default
#: sweep.
DEFAULT_RUNGS = [r for r in RUNGS
                 if r not in ("decompose_1e8_grid", "decompose_1e8_ba",
                              "rehearse_1e8_ba_step",
                              "dryrun_multichip_mid",
                              "dryrun_repl_sweep")]


def main() -> None:
    if len(sys.argv) == 3 and sys.argv[1] == "--rung":
        print(json.dumps(RUNGS[sys.argv[2]]()), flush=True)
        return
    rungs = sys.argv[1:] or list(DEFAULT_RUNGS)
    unknown = [r for r in rungs if r not in RUNGS]
    if unknown:
        raise SystemExit(f"unknown rung(s) {unknown}; "
                         f"valid: {sorted(RUNGS)}")
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    results = {}
    if os.path.exists(OUT):
        with open(OUT) as f:
            results = json.load(f)
    from arrow_matrix_tpu.utils.platform import host_load

    for rung in rungs:
        print(f"[ladder] {rung} ...", flush=True)
        load_before = host_load()
        t0 = time.perf_counter()
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--rung", rung],
            capture_output=True, text=True)
        wall = round(time.perf_counter() - t0, 1)
        if proc.returncode == 0 and proc.stdout.strip():
            new = json.loads(proc.stdout.strip().splitlines()[-1])
            new["wall_s"] = wall
            # Measurement hygiene (VERDICT item 6): each committed rung
            # records the host contention it ran under, both ends.
            new["host_load"] = {"before": load_before,
                                "after": host_load()}
            if new.get("cached"):
                # A cache hit never becomes the rung's RESULT: either
                # the recorded measured numbers stay (they are the
                # provenance PERFORMANCE.md cites), or — with no clean
                # prior entry — the stub is reported but NOT recorded
                # (delete the artifact to re-measure).
                prior_ok = (rung in results
                            and "error" not in results[rung]
                            and not results[rung].get("cached"))
                print(f"[ladder] {rung}: cached artifact; "
                      f"{'keeping recorded numbers' if prior_ok else 'no recorded numbers — delete ' + str(new.get('base')) + '* to re-measure'}",
                      flush=True)
                continue
            results[rung] = new
            print(f"[ladder] {rung}: {results[rung]}", flush=True)
            # graft-ledger: each measured rung also lands in the
            # append-only store (the committed scale_ladder.json stays
            # the human-facing artifact; the ledger is the queryable
            # history the drift gate bands on).
            try:
                from arrow_matrix_tpu.ledger import (
                    record as _ledger_record,
                )

                load_after = new.get("host_load", {}).get("after", {})
                _ledger_record(
                    "ladder", f"ladder_{rung}_wall_s", wall, unit="s",
                    host_load=load_after.get("loadavg_1m"),
                    knobs={"rung": rung},
                    payload={k: v for k, v in new.items()
                             if not isinstance(v, (dict, list))})
            except Exception as e:
                print(f"[ledger] ladder record not persisted: "
                      f"{type(e).__name__}: {e}", file=sys.stderr)
        else:
            failure = {"error": proc.stderr.strip()[-500:],
                       "wall_s": wall}
            if rung in results and "error" not in results[rung]:
                # A failed RE-run (e.g. resource exhaustion from
                # concurrent host load) must not destroy recorded
                # gate-passing provenance; park it alongside.
                results[rung + "_retry_error"] = failure
                print(f"[ladder] {rung} retry FAILED (recorded "
                      f"numbers kept): {failure['error'][-160:]}",
                      flush=True)
            else:
                results[rung] = failure
                print(f"[ladder] {rung} FAILED: {failure}", flush=True)
        with open(OUT, "w") as f:
            json.dump(results, f, indent=1)
    print(json.dumps(results, indent=1))


if __name__ == "__main__":
    main()
