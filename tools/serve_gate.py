#!/usr/bin/env python
"""Tier-1 serve gate: chaos-under-load for the graft-serve runtime.

The serving counterpart of tools/chaos_gate.py (which imports these
scenarios into its matrix): a 4-tenant synthetic trace runs against an
:class:`~arrow_matrix_tpu.serve.ArrowServer` over a small BA resident
operator while faults land mid-flight, and every scenario must end
**detected** + **recovered** (or cleanly, explicitly shed) with every
surviving request's result **bit-identical** to a fault-free replay —
and the server process never needing an external restart:

  serve_hang     — an injected stall outlasts the per-request
                   watchdog while 4 tenants are queued; the request is
                   retried and every request still completes.
  serve_corrupt  — a corrupted per-request checkpoint (bad bytes +
                   mismatched sha256 sidecar) is planted before the
                   run; the resume detects it loudly, discards, and
                   recomputes — under a full queue of other tenants.
  serve_overflow — a burst past the bounded queue: the overflow is
                   shed EXPLICITLY (deterministic count, ticket state
                   + reason, flight event), admitted requests are
                   untouched.
  serve_hbm      — an HBM budget with headroom for exactly one
                   request's carriage: admission rejects the rest
                   429-style with zero over-budget admissions
                   (verified against the memview price).
  serve_classes  — graft-classes: approx (bf16) tenants under a real
                   probed certificate serve reduced-precision carriage
                   within the class tolerance of the f32 replay, exact
                   tenants in the same run stay bit-identical, approx
                   admission is priced below exact, and an
                   uncertifiable request (deeper than the curve) falls
                   back to exact with an explicit reason.
  serve_kill     — (subprocess; skipped under ``--fast``) SIGKILL
                   lands mid-request in a checkpointing graft_serve
                   CLI run; the rerun resumes in-flight requests from
                   their sha256-verified checkpoints and the full
                   result set is bit-identical to a never-killed run.
  slo_burn_degrade — sustained fault pressure under a deterministic
                   pulse clock: the graft-pulse SLO-burn watchdog
                   trips after exactly ``min_windows`` burning windows
                   (hysteresis: the first faulty window alone never
                   fires), feeds the degradation ladder
                   (``slo_burn:fault_rate`` rung), emits the
                   ``slo_burn_cleared`` recovery event on the first
                   healthy window — and every request completes
                   bit-identical to a fault-free run on the same base
                   rung.  The whole pass is replayed and must
                   reproduce the identical burn-event sequence.

Exits 0 when every scenario passes, 1 otherwise.

Usage:
  python tools/serve_gate.py [--fast] [workdir]
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

N, WIDTH, K = 128, 16, 2
TENANTS, REQUESTS, ITERS = 4, 8, 4
SEED = 11


def _policy(**kw):
    from arrow_matrix_tpu.faults import RetryPolicy

    base = dict(max_retries=2, backoff_s=0.01, jitter=0.2, seed=SEED)
    base.update(kw)
    return RetryPolicy(**base)


def _server(factory, **kw):
    from arrow_matrix_tpu.serve import ArrowServer, ExecConfig

    base = dict(queue_capacity=16, policy=_policy(), name="gate")
    base.update(kw)
    return ArrowServer(factory, ExecConfig(), **base)


def _trace(n_rows):
    from arrow_matrix_tpu.serve import synthetic_trace

    return synthetic_trace(n_rows, tenants=TENANTS, requests=REQUESTS,
                           k=K, iterations=ITERS, seed=SEED)


def _run(server, n_rows):
    from arrow_matrix_tpu.serve import run_trace

    return run_trace(server, _trace(n_rows))


def _result_bytes(tickets) -> dict:
    return {t.request.request_id: t.result.tobytes()
            for t in tickets if t.result is not None}


def scenario_serve_hang(factory, n_rows, ref):
    """Watchdog-timeout recovery with 4 tenants in flight."""
    from arrow_matrix_tpu import faults

    faults.set_plan({"scenario": "hang", "site": "multi_level.step",
                     "after": 3, "hang_s": 1.0})
    srv = _server(factory,
                  policy=_policy(watchdog_s=0.3,
                                 watchdog_grace_s=60.0))
    try:
        tickets = _run(srv, n_rows)
    finally:
        faults.clear_plan()
    problems = []
    s = srv.summary()
    if s["completed"] != REQUESTS:
        problems.append(f"serve_hang: {s['completed']}/{REQUESTS} "
                        f"requests completed")
    if srv.faults_seen == 0:
        problems.append("serve_hang: the watchdog never fired on the "
                        "injected stall")
    if srv.recoveries == 0:
        problems.append("serve_hang: no recovery was taken")
    if _result_bytes(tickets) != ref:
        problems.append("serve_hang: surviving results are not "
                        "bit-identical to the fault-free replay")
    return problems


def scenario_serve_corrupt(factory, n_rows, ref, workdir):
    """A corrupted per-request checkpoint under a full queue: the
    sha256 sidecar fails the resume loudly; the server discards the
    checkpoint and recomputes — never crashes, never serves poison."""
    ckdir = os.path.join(workdir, "serve_ck_corrupt")
    os.makedirs(ckdir, exist_ok=True)
    # Plant a corrupt npz checkpoint for request r0000 (unbatched key):
    # garbage npz bytes plus a sidecar recording a different digest —
    # exactly what post-write disk corruption looks like.
    victim = os.path.join(ckdir, "ck_r0000.npz")
    with open(victim, "wb") as fh:
        fh.write(b"\x00corrupt\xff" * 64)
    with open(victim + ".sha256", "w", encoding="utf-8") as fh:
        fh.write("0" * 64 + "\n")
    srv = _server(factory, checkpoint_dir=ckdir, checkpoint_every=2)
    tickets = _run(srv, n_rows)
    problems = []
    s = srv.summary()
    if s["checkpoint_corruptions"] < 1:
        problems.append("serve_corrupt: the corrupted checkpoint was "
                        "not detected")
    if s["completed"] != REQUESTS:
        problems.append(f"serve_corrupt: {s['completed']}/{REQUESTS} "
                        f"requests completed")
    if _result_bytes(tickets) != ref:
        problems.append("serve_corrupt: recomputed results are not "
                        "bit-identical to the fault-free replay")
    if os.path.exists(victim):
        problems.append("serve_corrupt: the corrupt checkpoint was "
                        "not discarded")
    return problems


def scenario_serve_overflow(factory, n_rows, ref):
    """Burst past the bounded queue: deterministic, explicit shed."""
    capacity = 3
    srv = _server(factory, queue_capacity=capacity)
    trace = _trace(n_rows)
    tickets = [srv.submit(r) for r in trace]   # burst: no draining
    srv.drain()
    problems = []
    s = srv.summary()
    want_shed = REQUESTS - capacity
    if s["shed"] != want_shed or s["completed"] != capacity:
        problems.append(
            f"serve_overflow: expected exactly {capacity} completed + "
            f"{want_shed} shed, got {s['completed']} + {s['shed']}")
    for t in tickets:
        if not t.done:
            problems.append(f"serve_overflow: request "
                            f"{t.request.request_id} never reached a "
                            f"terminal state (silently dropped)")
        elif t.status == "shed" and t.reason != "queue_full":
            problems.append(f"serve_overflow: shed request "
                            f"{t.request.request_id} lacks the "
                            f"explicit queue_full reason")
    got = _result_bytes(tickets)
    for rid, payload in got.items():
        if ref.get(rid) != payload:
            problems.append(f"serve_overflow: surviving request {rid} "
                            f"is not bit-identical to the fault-free "
                            f"replay")
    # Replay determinism: the same burst sheds the same census.
    srv2 = _server(factory, queue_capacity=capacity)
    tickets2 = [srv2.submit(r) for r in _trace(n_rows)]
    srv2.drain()
    census = [(t.status, t.reason) for t in tickets]
    census2 = [(t.status, t.reason) for t in tickets2]
    if census != census2:
        problems.append("serve_overflow: the shed census is not "
                        "replay-deterministic")
    return problems


def scenario_serve_hbm(factory, n_rows, ref):
    """Admission control: headroom for exactly one request's carriage
    — the burst must see zero over-budget admissions, each rejection
    explicit, and the one admitted request completes bit-identically."""
    from arrow_matrix_tpu.serve import ExecConfig, request_price_bytes

    executor = factory(ExecConfig())
    from arrow_matrix_tpu.obs.memview import predicted_bytes_for

    resident = predicted_bytes_for(executor, 0) or 0
    price = request_price_bytes(executor, K)
    srv = _server(factory, hbm_budget_bytes=resident + price)
    tickets = [srv.submit(r) for r in _trace(n_rows)]   # burst
    srv.drain()
    problems = []
    s = srv.summary()
    if s["admitted"] != 1 or s["rejected"] != REQUESTS - 1:
        problems.append(
            f"serve_hbm: expected exactly 1 admission + "
            f"{REQUESTS - 1} rejections at a one-request budget, got "
            f"{s['admitted']} + {s['rejected']}")
    peak = s["hbm"]["peak_in_use_bytes"]
    if peak > resident + price:
        problems.append(f"serve_hbm: peak HBM {peak} B exceeded the "
                        f"budget {resident + price} B — an "
                        f"over-budget request was admitted")
    for t in tickets:
        if t.status == "rejected" and t.reason != "hbm_budget":
            problems.append(f"serve_hbm: rejected request "
                            f"{t.request.request_id} lacks the "
                            f"explicit hbm_budget reason")
    got = _result_bytes(tickets)
    for rid, payload in got.items():
        if ref.get(rid) != payload:
            problems.append(f"serve_hbm: admitted request {rid} is "
                            f"not bit-identical to the fault-free "
                            f"replay")
    return problems


def scenario_slo_burn_degrade(factory, n_rows):
    """Measured SLO pressure drives the same ladder faults do: two
    consecutive windows with injected (recovered) faults trip the
    ``fault_rate`` burn rule, the watchdog degrades the burning
    window's tenant one rung, the first healthy window clears the
    burn — all on a manual clock (one window per request), so the
    entire episode is replay-deterministic."""
    from arrow_matrix_tpu import faults
    from arrow_matrix_tpu.obs import flight, pulse
    from arrow_matrix_tpu.serve import ArrowServer, ExecConfig

    # The fused pallas_sell kernel gives the ladder a second rung
    # (-> xla) that accepts the same K, so a forced degradation has
    # somewhere to land; at this size both kernels sum every slot in
    # the same order, so the results stay bit-identical.
    base_cfg = ExecConfig(kernel="pallas_sell")

    def one_pass(inject):
        now = [0.0]
        mon = pulse.PulseMonitor(
            window_s=1.0, clock=lambda: now[0], name="gate-burn",
            watchdog=pulse.SloWatchdog(
                [pulse.BurnRule.fault_rate(0.0, min_windows=2)]))
        # degrade_after=100: the organic recovered-fault path cannot
        # reach a rung in this run; only the watchdog's forced score
        # (note_slo_pressure) can move the ladder.
        srv = ArrowServer(factory, base_cfg, queue_capacity=16,
                          policy=_policy(), degrade_after=100,
                          name="gate-burn")
        srv.attach_pulse(mon)
        tickets = []
        try:
            for i, r in enumerate(_trace(n_rows)):
                if inject and i < 2:
                    faults.set_plan({"scenario": "error",
                                     "site": "multi_level.step",
                                     "after": 0, "count": 1})
                else:
                    faults.clear_plan()
                tickets.append(srv.submit(r))
                srv.drain()
                now[0] += 1.0
                mon.advance()
        finally:
            faults.clear_plan()
        mon.close("scenario done")
        return srv, mon, tickets

    ref_srv, _, ref_tickets = one_pass(inject=False)
    if ref_srv.summary()["completed"] != REQUESTS:
        return ["slo_burn_degrade: fault-free reference run on the "
                "pallas_sell base rung did not complete every request"]
    ref = _result_bytes(ref_tickets)

    srv, mon, tickets = one_pass(inject=True)
    problems = []
    s = srv.summary()
    if s["completed"] != REQUESTS:
        problems.append(f"slo_burn_degrade: {s['completed']}/"
                        f"{REQUESTS} requests completed")

    # Hysteresis + trip: exactly one burn, at window 1 (the second
    # consecutive faulty window) — window 0 alone must never fire.
    burns = [(e["rule"], e["window"]) for e in mon.burn_events
             if e["event"] == "slo_burn"]
    if burns != [("fault_rate", 1)]:
        problems.append(f"slo_burn_degrade: expected one fault_rate "
                        f"burn at window 1, got {burns}")
    cleared = [(e["rule"], e["window"]) for e in mon.burn_events
               if e["event"] == "slo_burn_cleared"]
    if cleared != [("fault_rate", 2)]:
        problems.append(f"slo_burn_degrade: expected one recovery "
                        f"(slo_burn_cleared) at window 2, got "
                        f"{cleared}")
    faulty = [w["window"] for w in mon.series() if w["faults_seen"]]
    if faulty != [0, 1]:
        problems.append(f"slo_burn_degrade: injected faults landed in "
                        f"windows {faulty}, expected [0, 1]")

    # The burning window's tenant took exactly one forced rung with
    # the watchdog's reason attached.
    hits = [(name, d) for name, t in s["tenants"].items()
            for d in t["degradations"]]
    burn_hits = [(name, d) for name, d in hits
                 if d["reason"] == "slo_burn:fault_rate"]
    if len(burn_hits) != 1:
        problems.append(f"slo_burn_degrade: expected exactly one "
                        f"slo_burn:fault_rate degradation, got "
                        f"{[(n, d['reason']) for n, d in hits]}")
    else:
        name, d = burn_hits[0]
        if s["tenants"][name]["rung"] != 1 \
                or d["to"]["kernel"] != "xla":
            problems.append(
                f"slo_burn_degrade: tenant {name} should sit on rung "
                f"1 (kernel xla), got rung "
                f"{s['tenants'][name]['rung']} -> {d['to']}")

    if _result_bytes(tickets) != ref:
        problems.append("slo_burn_degrade: surviving results are not "
                        "bit-identical to the fault-free run on the "
                        "same base rung")
    rec = flight.get_recorder()
    if rec is not None:
        kinds = {e.get("kind") for e in rec.events}
        if "slo_burn" not in kinds:
            problems.append("slo_burn_degrade: the watchdog trip left "
                            "no slo_burn flight event")

    # Replay determinism: the identical pass reproduces the identical
    # burn-event sequence, ticket census, and result bytes.
    srv2, mon2, tickets2 = one_pass(inject=True)
    seq = [(e["event"], e["rule"], e["window"])
           for e in mon.burn_events]
    seq2 = [(e["event"], e["rule"], e["window"])
            for e in mon2.burn_events]
    if seq != seq2:
        problems.append(f"slo_burn_degrade: burn-event sequence is "
                        f"not replay-deterministic: {seq} vs {seq2}")
    if [(t.status, t.reason) for t in tickets] != \
            [(t.status, t.reason) for t in tickets2]:
        problems.append("slo_burn_degrade: the ticket census is not "
                        "replay-deterministic")
    if _result_bytes(tickets2) != _result_bytes(tickets):
        problems.append("slo_burn_degrade: replayed results are not "
                        "bit-identical")
    return problems


def scenario_serve_classes(factory, n_rows, ref):
    """graft-classes: approx (bf16) tenants under a REAL probed
    certificate serve reduced-precision carriage — their results land
    within the class tolerance of the f32 replay and are NOT the f32
    bits (the cheaper carriage actually ran) — while exact tenants in
    the same run stay bit-identical, approx admission is priced below
    exact at the same k, an uncertifiable request (iterations beyond
    the curve) falls back to exact LOUDLY, and the whole pass is
    replay-deterministic."""
    import dataclasses

    import numpy as np

    from arrow_matrix_tpu.classes import certificate_from_record
    from arrow_matrix_tpu.ledger.probe import error_curves_for_source
    from arrow_matrix_tpu.serve import run_trace

    # The certificate comes from the probe, never from hand: the same
    # (structure, seed) the gate's factory builds, probed at bf16.
    source = {"kind": "ba", "n": N, "m": 3, "width": WIDTH,
              "seed": SEED}
    recs = error_curves_for_source(source, k=K, iterations=ITERS,
                                   seed=SEED, dtypes=("bf16",))
    cert = certificate_from_record(recs[0])
    if cert is None or not cert.covers(ITERS):
        return [f"serve_classes: the probed bf16 curve does not "
                f"certify {ITERS} iterations "
                f"(curve={None if cert is None else cert.rel_frobenius})"]

    def classed(trace):
        return [dataclasses.replace(r, traffic_class="approx")
                if r.tenant in ("tenant0", "tenant1") else r
                for r in trace]

    def one_pass():
        srv = _server(factory, certificates=[cert])
        tickets = run_trace(srv, classed(_trace(n_rows)))
        return srv, tickets

    srv, tickets = one_pass()
    problems = []
    s = srv.summary()
    if s["completed"] != REQUESTS:
        problems.append(f"serve_classes: {s['completed']}/{REQUESTS} "
                        f"requests completed")
    approx = [t for t in tickets if t.request.traffic_class == "approx"]
    exact = [t for t in tickets if t.request.traffic_class == "exact"]
    if not approx or not exact:
        return [f"serve_classes: trace split degenerate "
                f"({len(approx)} approx / {len(exact)} exact)"]
    for t in approx:
        if t.served_class != "approx" or t.class_fallback is not None:
            problems.append(
                f"serve_classes: certified approx request "
                f"{t.request.request_id} was not served approx "
                f"(served={t.served_class}, "
                f"fallback={t.class_fallback})")
            continue
        if t.certified_bound != cert.bound_at(ITERS):
            problems.append(f"serve_classes: ticket "
                            f"{t.request.request_id} carries bound "
                            f"{t.certified_bound}, certificate says "
                            f"{cert.bound_at(ITERS)}")
        gold = np.frombuffer(ref[t.request.request_id],
                             dtype=np.float32).reshape(t.result.shape)
        d = t.result.astype(np.float64) - gold.astype(np.float64)
        rel = float(np.linalg.norm(d) / np.linalg.norm(
            gold.astype(np.float64)))
        if rel > cert.tolerance:
            problems.append(
                f"serve_classes: approx result "
                f"{t.request.request_id} drifted rel={rel:.3e} past "
                f"the class tolerance {cert.tolerance:.0e}")
        if rel == 0.0:
            problems.append(
                f"serve_classes: approx request "
                f"{t.request.request_id} returned the f32 bits — the "
                f"reduced carriage never ran")
    for t in exact:
        if t.request.request_id in ref and (
                t.result is None
                or t.result.tobytes() != ref[t.request.request_id]):
            problems.append(f"serve_classes: exact request "
                            f"{t.request.request_id} is not "
                            f"bit-identical beside approx traffic")
    # Class economics: approx reserved fewer bytes than exact at the
    # same (structure, k) — the admitted-requests-per-GB lever.
    if approx[0].predicted_bytes >= exact[0].predicted_bytes:
        problems.append(
            f"serve_classes: approx admission price "
            f"{approx[0].predicted_bytes} B is not below exact "
            f"{exact[0].predicted_bytes} B")
    # Uncertifiable: iterations beyond the measured curve must fall
    # back to exact with the explicit reason — never silent approx.
    deep = dataclasses.replace(_trace(n_rows)[0], iterations=ITERS + 2,
                               traffic_class="approx")
    t_deep = srv.submit(deep)
    srv.drain()
    if t_deep.status != "completed" or t_deep.served_class != "exact" \
            or t_deep.class_fallback != "curve_shorter_than_request":
        problems.append(
            f"serve_classes: beyond-curve approx request ended "
            f"{t_deep.status}/{t_deep.served_class} with fallback "
            f"{t_deep.class_fallback!r} (want completed/exact/"
            f"curve_shorter_than_request)")
    if s["classes"]["approx"]["completed"] != len(approx):
        problems.append(
            f"serve_classes: summary counts "
            f"{s['classes']['approx']['completed']} approx "
            f"completions, trace had {len(approx)}")
    # Replay determinism — approx carriage included.
    srv2, tickets2 = one_pass()
    if [(t.status, t.served_class, t.class_fallback)
            for t in tickets] != \
            [(t.status, t.served_class, t.class_fallback)
             for t in tickets2]:
        problems.append("serve_classes: the class census is not "
                        "replay-deterministic")
    if _result_bytes(tickets) != _result_bytes(tickets2):
        problems.append("serve_classes: approx results are not "
                        "replay-deterministic")
    return problems


def scenario_serve_kill(workdir):
    """SIGKILL mid-request in a checkpointing graft_serve CLI run; the
    rerun resumes and the result set is bit-identical to a never-
    killed run."""
    import numpy as np

    problems = []
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("AMT_FAULT_PLAN", None)
    ck = os.path.join(workdir, "serve_ck_kill")
    ref_npz = os.path.join(workdir, "serve_ref.npz")
    kill_npz = os.path.join(workdir, "serve_kill.npz")
    cmd = [sys.executable, "-m", "arrow_matrix_tpu.cli.graft_serve",
           "--vertices", str(N), "--width", str(WIDTH),
           "--features", str(K), "--tenants", str(TENANTS),
           "--requests", str(REQUESTS), "--iterations", str(ITERS),
           "--seed", str(SEED), "--device", "cpu",
           "--checkpoint_every", "2"]

    def run(extra, fault_env=None):
        e = dict(env)
        if fault_env:
            e["AMT_FAULT_PLAN"] = fault_env
        return subprocess.run(cmd + extra, env=e, cwd=workdir,
                              capture_output=True, text=True,
                              timeout=600)

    r = run(["--results_out", ref_npz])
    if r.returncode != 0:
        return [f"serve_kill: fault-free reference run failed rc="
                f"{r.returncode}: {r.stderr[-500:]}"]
    # 8 requests x 4 iterations = 32 step hits; hit 18 lands
    # mid-request-4 with four requests already completed (their final
    # checkpoints on disk) and a step-2 checkpoint for the victim.
    plan = json.dumps({"scenario": "kill", "site": "*.step",
                       "after": 18})
    r = run(["--results_out", kill_npz, "--checkpoint", ck],
            fault_env=plan)
    if r.returncode == 0:
        return ["serve_kill: injected SIGKILL did not terminate the "
                "server"]
    r = run(["--results_out", kill_npz, "--checkpoint", ck])
    if r.returncode != 0:
        return [f"serve_kill: resume run failed rc={r.returncode}: "
                f"{r.stderr[-500:]}"]
    if "resumed request" not in r.stdout:
        problems.append("serve_kill: rerun did not report resuming "
                        "any request from its checkpoint")
    with np.load(ref_npz) as a, np.load(kill_npz) as b:
        if sorted(a.files) != sorted(b.files):
            problems.append(f"serve_kill: result sets differ: "
                            f"{sorted(a.files)} vs {sorted(b.files)}")
        else:
            for rid in a.files:
                if a[rid].tobytes() != b[rid].tobytes():
                    problems.append(
                        f"serve_kill: resumed request {rid} is not "
                        f"bit-identical to the never-killed run")
    return problems


def run_serve_scenarios(workdir, fast=False):
    """Run the serving matrix; returns (problems, scenarios_run).
    Assumes the caller pinned the platform and (optionally) installed
    a flight recorder — tools/chaos_gate.py imports this into its
    matrix."""
    from arrow_matrix_tpu import faults
    from arrow_matrix_tpu.serve import ba_executor_factory

    faults.clear_plan()
    factory, n_rows = ba_executor_factory(N, WIDTH, SEED, fmt="fold")
    ref_srv = _server(factory)
    ref_tickets = _run(ref_srv, n_rows)
    if ref_srv.summary()["completed"] != REQUESTS:
        return (["serve baseline: fault-free serve run did not "
                 "complete every request"], [])
    ref = _result_bytes(ref_tickets)
    problems = []
    scenarios = ["serve_hang", "serve_corrupt", "serve_overflow",
                 "serve_hbm", "slo_burn_degrade", "serve_classes"]
    problems += scenario_serve_hang(factory, n_rows, ref)
    problems += scenario_serve_corrupt(factory, n_rows, ref, workdir)
    problems += scenario_serve_overflow(factory, n_rows, ref)
    problems += scenario_serve_hbm(factory, n_rows, ref)
    problems += scenario_slo_burn_degrade(factory, n_rows)
    problems += scenario_serve_classes(factory, n_rows, ref)
    if not fast:
        scenarios.append("serve_kill")
        problems += scenario_serve_kill(workdir)
    return problems, scenarios


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    fast = "--fast" in argv
    argv = [a for a in argv if a != "--fast"]

    from arrow_matrix_tpu.utils.platform import force_cpu_devices

    force_cpu_devices(4)

    import tempfile

    from arrow_matrix_tpu import sync
    from arrow_matrix_tpu.obs import flight

    # Arm the lock-order witness before any server is constructed so
    # every scenario below doubles as a lock-order execution test
    # (sync.py module docstring).  An inverted acquisition raises
    # LockOrderViolation inside the scenario and fails the gate.
    registry = sync.enable_witness()

    workdir = argv[0] if argv else tempfile.mkdtemp(prefix="serve_gate_")
    os.makedirs(workdir, exist_ok=True)
    rec = flight.FlightRecorder(os.path.join(workdir, "flight.json"))
    flight.set_recorder(rec)
    try:
        problems, scenarios = run_serve_scenarios(workdir, fast=fast)
        kinds = {e.get("kind") for e in rec.events}
        if "serve" not in kinds:
            problems.append(f"flight recorder saw kinds "
                            f"{sorted(kinds)} — serve events are "
                            f"required")
    finally:
        rec.seal("serve gate done")
        flight.set_recorder(None)
    snap = registry.snapshot()
    if snap["violations"]:
        problems.extend(f"lock witness: {v}" for v in snap["violations"])
    if not snap["acquisitions"]:
        problems.append("lock witness: zero witnessed acquisitions — "
                        "the serving stack stopped routing its locks "
                        "through sync.witnessed()")
    print(f"serve gate: lock witness — {snap['acquisitions']} "
          f"acquisitions, {snap['reentries']} reentries, "
          f"{len(snap['threads'])} threads, "
          f"{len(snap['observed_edges'])} observed edges, "
          f"{len(snap['violations'])} violations", file=sys.stderr)
    if problems:
        for p in problems:
            print(f"serve gate: {p}", file=sys.stderr)
        print("serve gate: FAILED", file=sys.stderr)
        return 1
    print(f"serve gate: ok — scenarios {'+'.join(scenarios)} "
          f"detected, recovered (or explicitly shed), bit-identical "
          f"({workdir})", file=sys.stderr)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
