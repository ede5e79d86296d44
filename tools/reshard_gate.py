#!/usr/bin/env python
"""Reshard chaos gate: kill-mid-migration survival + the H7
bounded-scratch law (graft-reshard).

The acceptance bar for staged redistribution (parallel/reshard.py):

* **reshard_h7** — the static half.  Re-derives a staged shuffle at a
  tiny scale, lowers every stage of the split route, and requires
  ``check_h7`` to PASS (every stage's per-device send+recv collective
  buffers <= the declared scratch budget) while the UNSPLIT one-shot
  route, fed to the same checker as a single "stage", must FAIL — the
  checker has to trip on exactly the memory cliff staging removes.
  Also audits bench_cache/hlo_manifest.json: at least two
  ``reshard[...]`` entries with H7 ``pass``, one of them a replication
  (repl c) change.
* **kill_mid_migration** — the live half.  A driver subprocess seeds
  one mid-flight (step 2 of 4) layout-tagged checkpoint per request on
  a 2-device layout, then grows the server to a 4-device layout
  (``ArrowServer.grow``: every checkpoint replayed through a staged
  plan with per-stage scratch <= a deliberately tiny budget) and
  serves the trace to completion.  Run A is fault-free (the
  bit-identity reference).  Run B arms ``AMT_FAULT_PLAN`` with a kill
  on the ``reshard.stage`` seam and SIGKILLs itself mid-cutover, after
  at least one checkpoint has already migrated.  Run C reruns run B's
  directory fault-free: grow must migrate ONLY the stragglers
  (1 <= migrated < all — proving the kill landed mid-migration and the
  rerun neither redoes nor skips everything), every request must
  RESUME (the ``resumed request`` line) and complete — zero lost
  accepted requests — and every f32 result must be bit-identical to
  run A.

Registered in tools/chaos_gate.py's matrix (the subprocess scenario
skips under ``--fast``, like serve_kill/fleet_kill).  Standalone:
``python tools/reshard_gate.py [workdir]``.
"""

import json
import os
import re
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# Driver scale: small enough for the CPU gate budget, big enough that
# the 2-dev -> 4-dev migration is genuinely staged at the tiny budget.
N, WIDTH, K = 96, 16, 2
TENANTS, REQUESTS, ITERS = 3, 6, 4
SEED, TRACE_SEED = 3, 7
#: Grow-migration scratch budget: at K=2/f32 a row is 8 B, so a stage
#: carries at most 256 // (2*8) = 16 rows per device — several stages
#: per 96-row checkpoint, so a kill can land strictly inside one.
DRIVER_BUDGET = 256
#: reshard.stage hits before the armed driver SIGKILLs itself: at the
#: 256 B budget each 96-row checkpoint migrates in 2 stages, so hit 9
#: is checkpoint 5's SECOND stage — strictly inside a cutover, with
#: four checkpoints already migrated and two stragglers left.
KILL_AFTER = 9

# H7 scenario scale (in-process, runs even under --fast).
H7_N, H7_NDEV, H7_K = 64, 4, 2
#: Small enough that the one-shot route's send+recv overflows it (the
#: planted violation) while every split stage stays within it.
H7_BUDGET = 256

MANIFEST = os.path.join(REPO, "bench_cache", "hlo_manifest.json")


# -- driver (runs in a subprocess) ------------------------------------------

def driver(run_dir, results_npz):
    """Seed step-2 checkpoints on the 2-dev layout, grow to 4 devices
    (staged checkpoint migration — the kill site), serve the trace to
    completion, save results.  Exits nonzero if any request is lost."""
    from arrow_matrix_tpu.utils.platform import force_cpu_devices

    force_cpu_devices(4)
    import jax
    import numpy as np

    from arrow_matrix_tpu.parallel.mesh import make_mesh
    from arrow_matrix_tpu.serve.loadgen import (
        ba_executor_factory,
        synthetic_trace,
    )
    from arrow_matrix_tpu.serve.scheduler import ArrowServer, ExecConfig
    from arrow_matrix_tpu.utils.checkpoint import (
        list_checkpoints,
        save_state,
    )

    ck_dir = os.path.join(run_dir, "checkpoints")
    os.makedirs(ck_dir, exist_ok=True)
    devs = jax.devices()
    mesh2 = make_mesh((2,), ("blocks",), devices=np.asarray(devs[:2]))
    mesh4 = make_mesh((4,), ("blocks",), devices=np.asarray(devs))
    fac2, n_rows = ba_executor_factory(N, WIDTH, SEED, fmt="auto",
                                       mesh=mesh2)
    fac4, _ = ba_executor_factory(N, WIDTH, SEED, fmt="auto",
                                  mesh=mesh4)
    trace = synthetic_trace(n_rows, tenants=TENANTS,
                            requests=REQUESTS, k=K, iterations=ITERS,
                            seed=TRACE_SEED)

    # Seed a mid-flight checkpoint per request on the SOURCE layout —
    # but only for requests with no checkpoint at all: a rerun after a
    # kill must keep both already-migrated files and src-layout
    # stragglers exactly as the dead process left them.
    have = {os.path.basename(s) for s in list_checkpoints(ck_dir)}
    ex2 = fac2(ExecConfig())
    seeded = 0
    for r in trace:
        if f"ck_{r.request_id}" in have:
            continue
        x = ex2.set_features(r.x)
        for _ in range(2):
            x = ex2.step(x)
        save_state(os.path.join(ck_dir, f"ck_{r.request_id}"),
                   np.asarray(x), 2,
                   layout=f"serve/{r.request_id}/k{r.k}"
                          f"/it{r.iterations}")
        seeded += 1
    print(f"[reshard-driver] seeded {seeded} step-2 checkpoint(s) "
          f"on the 2-device layout", flush=True)

    server = ArrowServer(fac2, ExecConfig(), name="reshard",
                         checkpoint_dir=ck_dir, checkpoint_every=2,
                         max_batch_k=0, grow_factory=fac4,
                         reshard_budget_bytes=DRIVER_BUDGET)
    # The staged cutover — AMT_FAULT_PLAN's reshard.stage kill (if
    # armed) SIGKILLs this process somewhere inside this call.
    if not server.grow(reason="gate"):
        print("[reshard-driver] FAIL: grow refused", flush=True)
        return 1
    tickets = [server.submit(r) for r in trace]
    server.drain()
    lost = [t.request.request_id for t in tickets
            if t.result is None]
    if lost:
        print(f"[reshard-driver] FAIL: lost accepted request(s) "
              f"{lost}", flush=True)
        return 1
    not_resumed = [t.request.request_id for t in tickets
                   if t.resumed_step != 2]
    if not_resumed:
        print(f"[reshard-driver] FAIL: request(s) {not_resumed} did "
              f"not resume from the migrated step-2 checkpoint",
              flush=True)
        return 1
    np.savez(results_npz,
             **{t.request.request_id: np.asarray(t.result)
                for t in tickets})
    print(f"[reshard-driver] {len(tickets)} request(s) completed, "
          f"all resumed at iteration 2", flush=True)
    return 0


def _run_driver(workdir, tag, fault_plan=None):
    """One driver subprocess; returns (proc, run_dir, npz).  ``tag``
    also selects the run directory, so a rerun under the same tag
    resumes the previous run's checkpoints."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("AMT_FAULT_PLAN", None)
    if fault_plan is not None:
        env["AMT_FAULT_PLAN"] = json.dumps(fault_plan)
    run_dir = os.path.join(workdir, f"reshard_{tag}")
    os.makedirs(run_dir, exist_ok=True)
    npz = os.path.join(run_dir, "results.npz")
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--driver",
         run_dir, npz],
        env=env, capture_output=True, text=True, timeout=600)
    return proc, run_dir, npz


def _migrated_count(stdout):
    """Parse "N checkpoint(s) migrated" out of the grow line."""
    m = re.search(r"grew to .*?: (\d+) checkpoint\(s\) migrated "
                  r"through (\d+) staged plan step\(s\)", stdout)
    return (int(m.group(1)), int(m.group(2))) if m else (None, None)


# -- scenarios --------------------------------------------------------------

def scenario_kill_mid_migration(workdir):
    problems = []
    import numpy as np

    # Run A: fault-free reference.
    ref, _, ref_npz = _run_driver(workdir, "ref")
    if ref.returncode != 0:
        return [f"kill_mid_migration: fault-free reference run "
                f"failed (rc={ref.returncode}):\n{ref.stdout[-2000:]}"
                f"\n{ref.stderr[-2000:]}"]
    mig_a, stages_a = _migrated_count(ref.stdout)
    if mig_a != REQUESTS:
        problems.append(f"kill_mid_migration: reference grow migrated "
                        f"{mig_a} checkpoint(s), expected {REQUESTS}")
    if stages_a is not None and stages_a <= REQUESTS:
        problems.append(f"kill_mid_migration: reference migration ran "
                        f"{stages_a} total stage(s) for {REQUESTS} "
                        f"checkpoint(s) — not genuinely staged, the "
                        f"kill site cannot land mid-checkpoint")

    # Run B: SIGKILL on the KILL_AFTER-th reshard.stage crossing.
    kill, kill_dir, kill_npz = _run_driver(
        workdir, "kill",
        fault_plan={"scenario": "kill", "site": "reshard.stage",
                    "after": KILL_AFTER})
    if kill.returncode == 0:
        problems.append("kill_mid_migration: armed run exited 0 — the "
                        "injected SIGKILL never fired on the "
                        "reshard.stage seam")

    # Run C: rerun the killed run's directory fault-free.
    resume, _, _ = _run_driver(workdir, "kill")
    if resume.returncode != 0:
        problems.append(f"kill_mid_migration: resume rerun failed "
                        f"(rc={resume.returncode}):"
                        f"\n{resume.stdout[-2000:]}"
                        f"\n{resume.stderr[-2000:]}")
        return problems
    mig_c, _ = _migrated_count(resume.stdout)
    if mig_c is None or not (1 <= mig_c < REQUESTS):
        problems.append(f"kill_mid_migration: resume grow migrated "
                        f"{mig_c} checkpoint(s); the kill should have "
                        f"left between 1 and {REQUESTS - 1} "
                        f"stragglers (landed mid-migration)")
    if "resumed request" not in resume.stdout:
        problems.append("kill_mid_migration: resume run printed no "
                        "'resumed request' line — requests were "
                        "recomputed, not resumed")
    a = np.load(ref_npz)
    c = np.load(kill_npz)
    if sorted(a.files) != sorted(c.files):
        problems.append(f"kill_mid_migration: resume completed "
                        f"{sorted(c.files)} but the reference "
                        f"completed {sorted(a.files)} — lost "
                        f"accepted request(s)")
    else:
        for rid in a.files:
            if a[rid].tobytes() != c[rid].tobytes():
                problems.append(f"kill_mid_migration: result for "
                                f"{rid} is not bit-identical to the "
                                f"fault-free reference")
    return problems


def scenario_reshard_h7():
    problems = []
    import numpy as np

    # 1) Manifest audit: the proven H7 record this repo ships.
    if not os.path.exists(MANIFEST):
        problems.append(f"reshard_h7: {MANIFEST} missing — run "
                        f"tools/prove_collectives.py")
    else:
        with open(MANIFEST, encoding="utf-8") as fh:
            man = json.load(fh)
        entries = [e for e in man.get("entries", [])
                   if e.get("entry", "").startswith("reshard[")]
        passed = [e for e in entries
                  if e.get("rules", {}).get("H7", {})
                       .get("status") == "pass"]
        if len(passed) < 2:
            problems.append(f"reshard_h7: manifest has "
                            f"{len(passed)} reshard entr(ies) with "
                            f"H7 pass, need >= 2")
        if not any("repl" in e.get("entry", "") for e in passed):
            problems.append("reshard_h7: no H7-passing reshard entry "
                            "covers a replication (repl c) change")

    # 2) Live lowering: split stages must PASS, the one-shot route
    #    must FAIL the same checker (planted violation).
    import jax

    from arrow_matrix_tpu.analysis.contracts import CollectiveContract
    from arrow_matrix_tpu.analysis.prove import check_h7, summarize_hlo
    from arrow_matrix_tpu.parallel import routing as routing_mod
    from arrow_matrix_tpu.parallel.mesh import make_mesh, put_global
    from arrow_matrix_tpu.parallel.reshard import (
        Layout,
        plan_route_table,
        redistribution_plan,
    )
    from jax.sharding import NamedSharding, PartitionSpec

    devs = np.asarray(jax.devices()[:H7_NDEV])
    mesh = make_mesh((H7_NDEV,), ("blocks",), devices=devs)
    rng = np.random.default_rng(29)
    src = Layout(H7_N, n_dev=H7_NDEV, tag="gate_src")
    dst = Layout(H7_N, n_dev=H7_NDEV, tag="gate_dst")
    plan = redistribution_plan(src, dst, H7_BUDGET, k=H7_K,
                               perm_map=rng.permutation(H7_N)
                               .astype(np.int64))
    tbl, mask = plan_route_table(plan)
    route = routing_mod.build_route(tbl, H7_NDEV,
                                    src_total=src.stored_rows,
                                    pad_mask=mask)
    sroute = routing_mod.split_route_stages(route, H7_K, H7_BUDGET)
    contract = CollectiveContract(
        algorithm="gate_shuffle",
        step_bytes=route.device_bytes_per_exchange(H7_K, 4),
        reduce_bytes=0, repl=1, overlap_slabs=1, dtype="f32",
        lowered_kinds=("all-to-all",), compiled_kinds=("all-to-all",),
        ratio_band=(0.99, 1.01), scratch_budget_bytes=H7_BUDGET)
    x = put_global(
        rng.standard_normal((src.stored_rows, H7_K))
        .astype(np.float32),
        NamedSharding(mesh, PartitionSpec("blocks")))

    def _summ(rt):
        fn = jax.jit(lambda xx: routing_mod.routed_take(
            xx, routing_mod.shard_route(rt, mesh, "blocks"), mesh,
            "blocks"))
        return summarize_hlo(fn.lower(x).as_text(dialect="hlo"))

    staged = check_h7([_summ(st) for st in sroute.stages], contract)
    if staged["status"] != "pass":
        problems.append(f"reshard_h7: split route failed the checker "
                        f"it was built to satisfy: {staged['detail']}")
    one_shot = check_h7([_summ(route)], contract)
    if one_shot["status"] != "fail":
        problems.append(f"reshard_h7: one-shot route "
                        f"({route.device_bytes_per_exchange(H7_K, 4)}"
                        f" B/device) did NOT trip H7 at budget "
                        f"{H7_BUDGET} B — the checker cannot see the "
                        f"memory cliff (got {one_shot['status']}: "
                        f"{one_shot['detail']})")
    if sroute.n_stages < 2:
        problems.append(f"reshard_h7: split produced "
                        f"{sroute.n_stages} stage(s) — the gate "
                        f"scale no longer exercises staging")
    return problems


def run_reshard_scenarios(workdir, fast=False):
    """Chaos-gate entry point: returns (problems, scenario names)."""
    problems, scenarios = [], []

    scenarios.append("reshard_h7")
    problems += scenario_reshard_h7()

    if not fast:
        scenarios.append("kill_mid_migration")
        problems += scenario_kill_mid_migration(workdir)
    return problems, scenarios


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if argv and argv[0] == "--driver":
        return driver(argv[1], argv[2])
    from arrow_matrix_tpu.utils.platform import force_cpu_devices

    force_cpu_devices(4)
    fast = "--fast" in argv
    argv = [a for a in argv if a != "--fast"]

    from arrow_matrix_tpu import sync

    # Arm the lock-order witness so the migration scenarios (flock'd
    # sidecars + live-grow server) run order-checked; the
    # kill_mid_migration driver subprocess inherits AMT_LOCK_WITNESS
    # from the environment.
    registry = sync.enable_witness()

    if argv:
        workdir = argv[0]
        os.makedirs(workdir, exist_ok=True)
    else:
        import tempfile

        workdir = tempfile.mkdtemp(prefix="reshard_gate_")
    problems, scenarios = run_reshard_scenarios(workdir, fast=fast)
    snap = registry.snapshot()
    if snap["violations"]:
        problems.extend(f"lock witness: {v}" for v in snap["violations"])
    print(f"reshard gate: lock witness — {snap['acquisitions']} "
          f"acquisitions, {len(snap['threads'])} threads, "
          f"{len(snap['violations'])} violations")
    print(f"reshard gate scenarios: {scenarios}")
    if problems:
        print("RESHARD GATE: FAIL")
        for p in problems:
            print(f"  - {p}")
        return 1
    print("RESHARD GATE: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
