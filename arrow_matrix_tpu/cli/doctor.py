"""``amt_doctor`` — environment diagnosis for the framework.

Packages the operational knowledge the other entry points depend on
into one read-only command: which JAX backend the default platform
gives (discovered in a child process, so the doctor never holds the
chip), how many devices a virtual CPU pool would give, whether the
native C++ decomposer builds, whether cross-process collectives are
available, and the state of the benchmark caches.

Prints one human-readable report and exits 0 when the core checks
pass (a TPU is reported but NOT required — the framework's CPU paths
are first-class for tests and rehearsals).
"""

from __future__ import annotations

import argparse
import os
import subprocess
import sys


def _check(label: str, ok, detail: str = "") -> bool:
    mark = {True: "ok  ", False: "FAIL", None: "warn"}[ok]
    print(f"[{mark}] {label}" + (f": {detail}" if detail else ""),
          flush=True)
    return ok is not False


def probe_accelerator(timeout_s: float) -> tuple[bool, str]:
    """The DEFAULT backend's devices, discovered in a child process
    (``utils.platform.child_platform``)."""
    from arrow_matrix_tpu.utils.platform import child_platform

    try:
        found = child_platform(timeout_s=timeout_s)
    except RuntimeError as e:
        return False, str(e)
    return (found["platform"] == "tpu",
            f"{found['count']} x {found['platform']} {found['kind']}")


def probe_cpu_pool(n: int) -> tuple[bool, str]:
    code = (f"import sys; sys.argv=[]; "
            f"from arrow_matrix_tpu.utils.platform import "
            f"force_cpu_devices; force_cpu_devices({n}); import jax; "
            f"print('POOL', len(jax.devices()), "
            f"jax.devices()[0].platform)")
    try:
        proc = subprocess.run([sys.executable, "-c", code],
                              capture_output=True, text=True,
                              timeout=120)
    except subprocess.TimeoutExpired:
        return False, "no response in 120s"
    if proc.returncode != 0:
        return False, proc.stderr.strip()[-120:] or f"rc={proc.returncode}"
    # Last-line anchoring: a site plugin may print a banner first.
    lines = [ln for ln in proc.stdout.splitlines() if ln.startswith("POOL")]
    got = lines[-1].split()[1:] if lines else []
    return got == [str(n), "cpu"], (f"{got[0]} virtual cpu devices"
                                    if got else "no probe output")


def probe_gloo() -> tuple[bool | None, str]:
    try:
        import jax

        impl = jax.config.jax_cpu_collectives_implementation
        return True, (f"cpu collectives impl available "
                      f"(current: {impl or 'default'})")
    except (ImportError, AttributeError) as e:
        return None, (f"cpu-collectives knob unavailable ({e}); "
                      f"multi-process CPU runs may not work")


def probe_lint() -> tuple[bool, str]:
    """Run graft-lint (analysis/) over the installed package — a core
    check: a finding means a hot-path hazard (host sync, recompile,
    sharding mismatch) shipped past the gate."""
    try:
        import arrow_matrix_tpu
        from arrow_matrix_tpu.analysis import lint_paths

        pkg = os.path.dirname(os.path.abspath(arrow_matrix_tpu.__file__))
        findings, waived = lint_paths([pkg])
        if findings:
            worst = findings[0]
            return False, (f"{len(findings)} finding(s), e.g. "
                           f"{worst.format()[:100]}")
        return True, (f"clean ({len(waived)} waived) — "
                      f"run `python -m arrow_matrix_tpu.analysis` "
                      f"for details")
    except Exception as e:  # the doctor must never crash on a probe
        return False, f"{type(e).__name__}: {str(e)[:100]}"


def probe_prove() -> tuple[bool, str]:
    """graft-prove health: the H1-H3 checkers must trip on a planted
    surprise all-gather (in-process selftest, host-only), and the
    checked-in HLO contract manifest — when the working tree carries
    one — must record every contract proven.  The full prover
    (`python -m arrow_matrix_tpu.analysis prove`) compiles on a
    virtual mesh and is the lint_gate/--prove and tier-1 job, not a
    doctor probe."""
    try:
        from arrow_matrix_tpu.analysis import prove

        if not prove.selftest():
            return False, ("selftest failed: a planted surprise "
                           "all-gather did not trip H1-H3")
        mpath = prove.DEFAULT_MANIFEST
        if os.path.isfile(mpath):
            import json

            with open(mpath, encoding="utf-8") as fh:
                manifest = json.load(fh)
            if not manifest.get("ok"):
                return False, f"{mpath} records violated contracts"
            detail = (f"gate trips on planted surprises; {mpath}: "
                      f"{len(manifest.get('entries', ()))} entries ok")
        else:
            detail = ("gate trips on planted surprises; no checked-in "
                      "manifest here — run `python -m "
                      "arrow_matrix_tpu.analysis prove`")
        return True, detail
    except Exception as e:  # the doctor must never crash on a probe
        return False, f"{type(e).__name__}: {str(e)[:100]}"


def probe_sync() -> tuple[bool, str]:
    """graft-sync health: the RC1-RC5 analyzer must trip on its
    broken twins and the runtime witness must raise on an inverted
    acquisition order (in-process selftest, host-only); then one
    serve round trip runs in a bounded subprocess with
    AMT_LOCK_WITNESS=1 so every lock the request path takes is
    order-checked live.  The full static proof over the package is
    the lint_gate/--sync and tier-1 job, not a doctor probe."""
    try:
        from arrow_matrix_tpu.analysis import sync as graft_sync

        ok, lines = graft_sync.selftest()
        if not ok:
            bad = [ln for ln in lines if "fail" in ln.lower()]
            return False, ("selftest failed: "
                           + (bad[0] if bad else lines[-1]))[:140]
    except Exception as e:  # the doctor must never crash on a probe
        return False, f"{type(e).__name__}: {str(e)[:100]}"
    code = ("import sys, os, tempfile; sys.argv=[]; "
            "from arrow_matrix_tpu.utils.platform import "
            "force_cpu_devices; force_cpu_devices(1); "
            "from arrow_matrix_tpu import sync; "
            "assert sync.witness_registry() is not None, "
            "'witness did not arm from AMT_LOCK_WITNESS=1'; "
            "from arrow_matrix_tpu.serve import smoke_serve; "
            "d = tempfile.mkdtemp(prefix='sync_probe_'); "
            "s = smoke_serve(d, n=64, width=16, k=2, tenants=1, "
            "requests=1, iterations=1); "
            "reg = sync.witness_registry(); snap = reg.snapshot(); "
            "ok = (s['completed'] == 1 and s['failed'] == 0 and "
            "snap['acquisitions'] > 0 and not snap['violations']); "
            "print('SYNC ok ' + str(snap['acquisitions']) if ok "
            "else 'SYNC FAIL: ' + repr(snap))")
    env = dict(os.environ)
    env["AMT_LOCK_WITNESS"] = "1"
    try:
        proc = subprocess.run([sys.executable, "-c", code],
                              capture_output=True, text=True,
                              timeout=240, env=env)
    except subprocess.TimeoutExpired:
        return False, "no response in 240s"
    lines = [ln for ln in proc.stdout.splitlines()
             if ln.startswith("SYNC")]
    if proc.returncode != 0 or not lines:
        return False, (proc.stderr.strip()[-120:]
                       or f"rc={proc.returncode}, no probe output")
    if not lines[-1].startswith("SYNC ok"):
        return False, lines[-1][:120]
    acq = lines[-1].rsplit(" ", 1)[-1]
    return True, (f"twins trip, witness-on serve round-trips "
                  f"({acq} order-checked acquisitions, 0 violations)")


def probe_kcert() -> tuple[bool, str]:
    """graft-kcert health: the KC1-KC5 certifier must trip on its
    broken selftest twins (in-process, host-only — no jax import);
    then ONE certified kernel runs a full interpret-mode round trip
    in a bounded subprocess (certify_entry replays the DMA-ring
    schedule, enumerates the grid, and executes the numeric witness:
    stream == vectorized bit-identity vs the f32 golden).  The full
    two-kernel manifest check is kernel_gate/--kernels, not a doctor
    probe."""
    try:
        from arrow_matrix_tpu.analysis import kernels as graft_kcert

        ok, lines = graft_kcert.selftest()
        if not ok:
            bad = [ln for ln in lines if "fail" in ln.lower()]
            return False, ("selftest failed: "
                           + (bad[0] if bad else lines[-1]))[:140]
    except Exception as e:  # the doctor must never crash on a probe
        return False, f"{type(e).__name__}: {str(e)[:100]}"
    code = ("import sys; sys.argv=[]; "
            "from arrow_matrix_tpu.utils.platform import "
            "force_cpu_devices; force_cpu_devices(1); "
            "from arrow_matrix_tpu.ops.kernel_contract import "
            "builtin_kernels; "
            "from arrow_matrix_tpu.analysis.kernels import "
            "certify_entry; "
            "e = [x for x in builtin_kernels() "
            "if x.name == 'sell_tier_spmm_packed'][0]; "
            "rec = certify_entry(e); "
            "print('KCERT ok ' + str(rec['points']) if rec['ok'] "
            "else 'KCERT FAIL: ' + '; '.join(rec['findings'])[:200])")
    try:
        proc = subprocess.run([sys.executable, "-c", code],
                              capture_output=True, text=True,
                              timeout=240)
    except subprocess.TimeoutExpired:
        return False, "no response in 240s"
    lines = [ln for ln in proc.stdout.splitlines()
             if ln.startswith("KCERT")]
    if proc.returncode != 0 or not lines:
        return False, (proc.stderr.strip()[-120:]
                       or f"rc={proc.returncode}, no probe output")
    if not lines[-1].startswith("KCERT ok"):
        return False, lines[-1][:120]
    pts = lines[-1].rsplit(" ", 1)[-1]
    return True, (f"twins trip, certified interpret round trip "
                  f"({pts} grid/BlockSpec points, witness passed)")


def probe_obs() -> tuple[bool, str]:
    """graft-scope round-trip: the obs layer imports and a minimal
    smoke trace (one algorithm, 2 devices) produces a valid run
    directory — trace JSON, metrics.jsonl, summary.json.  Bounded
    subprocess: the probe must not inherit this process's backend
    state, and a wedged build must not hang the doctor."""
    code = ("import sys, tempfile; sys.argv=[]; "
            "from arrow_matrix_tpu.utils.platform import "
            "force_cpu_devices; force_cpu_devices(2); "
            "from arrow_matrix_tpu.obs.smoke import run_smoke, "
            "validate_run_dir; d = tempfile.mkdtemp(prefix='obs_probe_'); "
            "run_smoke(d, n=64, width=16, k=2, n_dev=2, iters=1, "
            "algorithms=('spmm_1d',)); p = validate_run_dir(d, "
            "algorithms=('spmm_1d',)); "
            "print('OBS ok' if not p else 'OBS FAIL: ' + p[0])")
    try:
        proc = subprocess.run([sys.executable, "-c", code],
                              capture_output=True, text=True,
                              timeout=240)
    except subprocess.TimeoutExpired:
        return False, "no response in 240s"
    lines = [ln for ln in proc.stdout.splitlines()
             if ln.startswith("OBS")]
    if proc.returncode != 0 or not lines:
        return False, (proc.stderr.strip()[-120:]
                       or f"rc={proc.returncode}, no probe output")
    if lines[-1] != "OBS ok":
        return False, lines[-1][:120]
    return True, ("smoke trace round-trips — run "
                  "`python -m arrow_matrix_tpu.obs smoke <dir>` for "
                  "the full five-algorithm run")


def probe_serve() -> tuple[bool, str]:
    """graft-serve round-trip: the serving runtime starts, admits and
    completes one request on the host-CPU backend, and shuts down
    cleanly with a valid SLO summary.  Bounded subprocess for the same
    reasons as the OBS probe: no backend-state inheritance, and a
    wedged build must not hang the doctor."""
    code = ("import sys, tempfile; sys.argv=[]; "
            "from arrow_matrix_tpu.utils.platform import "
            "force_cpu_devices; force_cpu_devices(1); "
            "from arrow_matrix_tpu.serve import smoke_serve; "
            "d = tempfile.mkdtemp(prefix='serve_probe_'); "
            "s = smoke_serve(d, n=64, width=16, k=2, tenants=1, "
            "requests=1, iterations=1); "
            "lat = s['latency_ms']; "
            "ok = (s['completed'] == 1 and s['failed'] == 0 and "
            "lat['p50'] is not None and lat['p99'] is not None and "
            "s['hbm']['budget_bytes'] > 0); "
            "print('SERVE ok' if ok else 'SERVE FAIL: ' + repr(s))")
    try:
        proc = subprocess.run([sys.executable, "-c", code],
                              capture_output=True, text=True,
                              timeout=240)
    except subprocess.TimeoutExpired:
        return False, "no response in 240s"
    lines = [ln for ln in proc.stdout.splitlines()
             if ln.startswith("SERVE")]
    if proc.returncode != 0 or not lines:
        return False, (proc.stderr.strip()[-120:]
                       or f"rc={proc.returncode}, no probe output")
    if lines[-1] != "SERVE ok":
        return False, lines[-1][:120]
    return True, ("one-request serve round-trips — run `graft_serve` "
                  "for the full multi-tenant load")


def probe_pulse() -> tuple[bool, str]:
    """graft-pulse round-trip: serve a two-request trace with a
    PulseMonitor attached, start the stdlib scrape endpoint on an
    ephemeral port, scrape /metrics and /pulse.json once, and validate
    both against the pulse schema.  Bounded subprocess, as for the OBS
    and SERVE probes."""
    code = (
        "import sys, json, urllib.request; sys.argv=[]; "
        "from arrow_matrix_tpu.utils.platform import "
        "force_cpu_devices; force_cpu_devices(1); "
        "from arrow_matrix_tpu.obs import pulse; "
        "from arrow_matrix_tpu.serve import ArrowServer, ExecConfig, "
        "ba_executor_factory, run_trace, synthetic_trace; "
        "fac, n = ba_executor_factory(64, 16, 3, fmt='fold'); "
        "mon = pulse.PulseMonitor(window_s=0.05, "
        "watchdog=pulse.SloWatchdog()); "
        "srv = ArrowServer(fac, ExecConfig(), name='pulse-probe'); "
        "srv.attach_pulse(mon); "
        "run_trace(srv, synthetic_trace(n, tenants=1, requests=2, "
        "k=2, iterations=1, seed=3)); mon.close(); "
        "ep = pulse.PulseEndpoint(mon); ep.start(); "
        "text = urllib.request.urlopen(ep.url + '/metrics', "
        "timeout=10).read().decode(); "
        "snap = json.loads(urllib.request.urlopen(ep.url + "
        "'/pulse.json', timeout=10).read().decode()); "
        "p = pulse.validate_exposition(text) + "
        "pulse.validate_ring(snap); ep.stop(); "
        "p += [] if snap['totals']['completed'] == 2 else "
        "['completed != 2']; "
        "print('PULSE ok' if not p else 'PULSE FAIL: ' + p[0])")
    try:
        proc = subprocess.run([sys.executable, "-c", code],
                              capture_output=True, text=True,
                              timeout=240)
    except subprocess.TimeoutExpired:
        return False, "no response in 240s"
    lines = [ln for ln in proc.stdout.splitlines()
             if ln.startswith("PULSE")]
    if proc.returncode != 0 or not lines:
        return False, (proc.stderr.strip()[-120:]
                       or f"rc={proc.returncode}, no probe output")
    if lines[-1] != "PULSE ok":
        return False, lines[-1][:120]
    return True, ("endpoint scrape + ring schema round-trip — run "
                  "`graft_serve --pulse` for the live series")


def probe_classes() -> tuple[bool, str]:
    """graft-classes round-trip: probe a bf16 error curve on a tiny BA
    structure, derive the certificate, and serve one approx request
    beside one exact request against it — the approx ticket must be
    served approx with a certified bound and a smaller admission price
    than the exact ticket at the same k.  Bounded subprocess, as for
    the SERVE probe."""
    code = (
        "import sys, dataclasses; sys.argv=[]; "
        "from arrow_matrix_tpu.utils.platform import "
        "force_cpu_devices; force_cpu_devices(1); "
        "from arrow_matrix_tpu.classes import certificate_from_record; "
        "from arrow_matrix_tpu.ledger.probe import "
        "error_curves_for_source; "
        "from arrow_matrix_tpu.serve import ArrowServer, ExecConfig, "
        "ba_executor_factory, run_trace, synthetic_trace; "
        "src = {'kind': 'ba', 'n': 64, 'm': 3, 'width': 16, "
        "'seed': 3}; "
        "recs = error_curves_for_source(src, k=2, iterations=2, "
        "seed=3, dtypes=('bf16',)); "
        "cert = certificate_from_record(recs[0]); "
        "fac, n = ba_executor_factory(64, 16, 3, fmt='fold'); "
        "srv = ArrowServer(fac, ExecConfig(), name='class-probe', "
        "certificates=[cert]); "
        "trace = [dataclasses.replace(r, traffic_class=c) for r, c "
        "in zip(synthetic_trace(n, tenants=1, requests=2, k=2, "
        "iterations=2, seed=3), ('approx', 'exact'))]; "
        "a, e = run_trace(srv, trace); "
        "ok = (cert is not None and cert.covers(2) and "
        "a.status == 'completed' and a.served_class == 'approx' and "
        "a.certified_bound is not None and "
        "a.predicted_bytes < e.predicted_bytes and "
        "e.status == 'completed' and e.served_class == 'exact'); "
        "print('CLASS ok' if ok else 'CLASS FAIL: ' + "
        "repr((a.summary(), e.summary())))")
    try:
        proc = subprocess.run([sys.executable, "-c", code],
                              capture_output=True, text=True,
                              timeout=240)
    except subprocess.TimeoutExpired:
        return False, "no response in 240s"
    lines = [ln for ln in proc.stdout.splitlines()
             if ln.startswith("CLASS")]
    if proc.returncode != 0 or not lines:
        return False, (proc.stderr.strip()[-120:]
                       or f"rc={proc.returncode}, no probe output")
    if lines[-1] != "CLASS ok":
        return False, lines[-1][:120]
    return True, ("bf16 certificate + approx round trip, priced "
                  "below exact — run `graft_ledger probe` for full "
                  "error curves")


def probe_tune() -> tuple[bool, str]:
    """graft-tune round-trip: one tiny smoke search races its
    subprocess children and persists a plan, and an immediate second
    search of the unchanged structure is a pure cache hit with ZERO
    children spawned — the acceptance property tools/tune_gate.py
    enforces.  Bounded subprocess, as for the SERVE and PULSE probes
    (force_cpu_devices sets env vars, so the tune children inherit
    the CPU pinning)."""
    code = ("import sys, tempfile; sys.argv=[]; "
            "from arrow_matrix_tpu.utils.platform import "
            "force_cpu_devices; force_cpu_devices(1); "
            "from arrow_matrix_tpu.tune import smoke_tune; "
            "d = tempfile.mkdtemp(prefix='tune_probe_'); "
            "r1 = smoke_tune(d); r2 = smoke_tune(d); "
            "ok = (r1['ok'] and not r1['cache_hit'] and "
            "r1['children_spawned'] > 0 and r2['ok'] and "
            "r2['cache_hit'] and r2['children_spawned'] == 0); "
            "print('TUNE ok' if ok else 'TUNE FAIL: ' + "
            "repr({'r1': {kk: r1.get(kk) for kk in ('ok', 'cache_hit', "
            "'children_spawned', 'error')}, 'r2': {kk: r2.get(kk) "
            "for kk in ('ok', 'cache_hit', 'children_spawned')}}))")
    try:
        proc = subprocess.run([sys.executable, "-c", code],
                              capture_output=True, text=True,
                              timeout=240)
    except subprocess.TimeoutExpired:
        return False, "no response in 240s"
    lines = [ln for ln in proc.stdout.splitlines()
             if ln.startswith("TUNE")]
    if proc.returncode != 0 or not lines:
        return False, (proc.stderr.strip()[-120:]
                       or f"rc={proc.returncode}, no probe output")
    if lines[-1] != "TUNE ok":
        return False, lines[-1][:120]
    return True, ("smoke search + pure cache hit round-trips — run "
                  "`graft_tune search` for a real structure")


def probe_ledger() -> tuple[bool, str]:
    """graft-ledger round-trip: append a record to a throwaway store
    and validate schema + hash chain; then, when the committed fixture
    store is present (tests/fixtures/ledger), run the drift gate
    against its baseline (must be green) AND verify a planted 10×
    regression trips it (the gate must not be green merely because it
    checks nothing).  Bounded subprocess, as for the other probes."""
    code = (
        "import sys, os, tempfile, json; sys.argv=[]; "
        "d = tempfile.mkdtemp(prefix='ledger_probe_'); "
        "from arrow_matrix_tpu.ledger import Ledger, "
        "canonical_record_id, schema_problems; "
        "from arrow_matrix_tpu.ledger import gate; "
        "lg = Ledger(d); "
        "r = lg.record('probe', 'doctor_probe_ms', 1.0, unit='ms', "
        "host_load=0.0, git_rev=None); "
        "p = schema_problems(r) + lg.validate(); "
        "fix = os.path.join('tests', 'fixtures', 'ledger'); "
        "bp = os.path.join(fix, 'baseline.json'); "
        "note = 'no committed fixture store — in-memory checks only'; "
        "fr = []; "
        "\n"
        "if os.path.isfile(bp):\n"
        "    flg = Ledger(fix); fr = flg.read_all()\n"
        "    base = gate.load_baseline(bp)\n"
        "    f, _ = gate.check_records(fr, base)\n"
        "    p += flg.validate() + f\n"
        "    banded = [x for x in fr if x.get('unit') in ('ms', 's') "
        "and isinstance(x.get('value'), (int, float))]\n"
        "    if banded:\n"
        "        bad = json.loads(json.dumps(banded[0]))\n"
        "        bad['value'] = bad['value'] * 10\n"
        "        bad['record_id'] = canonical_record_id(bad)\n"
        "        f2, _ = gate.check_records([bad], base)\n"
        "        if not f2:\n"
        "            p.append('planted 10x regression did not trip')\n"
        "    note = 'gate green on committed fixture; planted "
        "regression trips'\n"
        "print('LEDGER ok: ' + note if not p "
        "else 'LEDGER FAIL: ' + str(p[0]))")
    try:
        proc = subprocess.run([sys.executable, "-c", code],
                              capture_output=True, text=True,
                              timeout=240)
    except subprocess.TimeoutExpired:
        return False, "no response in 240s"
    lines = [ln for ln in proc.stdout.splitlines()
             if ln.startswith("LEDGER")]
    if proc.returncode != 0 or not lines:
        return False, (proc.stderr.strip()[-120:]
                       or f"rc={proc.returncode}, no probe output")
    if not lines[-1].startswith("LEDGER ok"):
        return False, lines[-1][:120]
    return True, lines[-1][len("LEDGER ok: "):][:120]


def probe_fleet() -> tuple[bool, str]:
    """graft-fleet round-trip: spawn a 2-worker process fleet, route
    one request to each worker, SIGKILL one, and require the router to
    requeue a request aimed at the dead worker onto the survivor — the
    kill-one-worker-of-N contract in miniature (tools/fleet_gate.py
    runs the full 3-worker mid-batch version).  Bounded subprocess, as
    for the other probes."""
    code = (
        "import sys, tempfile; sys.argv=[]; "
        "from arrow_matrix_tpu.utils.platform import "
        "force_cpu_devices; force_cpu_devices(1); "
        "import numpy as np; "
        "from arrow_matrix_tpu.fleet.router import FleetRouter; "
        "from arrow_matrix_tpu.serve.request import Request; "
        "d = tempfile.mkdtemp(prefix='fleet_probe_'); "
        "r = FleetRouter(spawn=2, vertices=64, width=16, seed=3, "
        "run_dir=d); p = []; "
        "\n"
        "try:\n"
        "    x = np.ones((r.n_rows, 2), dtype=np.float32)\n"
        "    wids = sorted(r.workers)\n"
        "    ten = {}\n"
        "    i = 0\n"
        "    while len(ten) < 2 and i < 256:\n"
        "        ten.setdefault(r.ring.lookup(f't{i}'), f't{i}')\n"
        "        i += 1\n"
        "    t1 = r.submit(Request('p0', ten[wids[0]], x, 1))\n"
        "    t2 = r.submit(Request('p1', ten[wids[1]], x, 1))\n"
        "    r.drain(timeout_s=120)\n"
        "    if not (t1.status == t2.status == 'completed'):\n"
        "        p.append('one-request-per-worker warmup failed: '\n"
        "                 + repr((t1.status, t2.status)))\n"
        "    victim = wids[0]\n"
        "    r.kill_worker(victim)\n"
        "    t3 = r.submit(Request('p2', ten[victim], x, 1))\n"
        "    r.drain(timeout_s=120)\n"
        "    if t3.status != 'completed':\n"
        "        p.append('requeued request did not complete: '\n"
        "                 + repr((t3.status, t3.reason, t3.error)))\n"
        "    elif getattr(t3, 'requeues', 0) < 1:\n"
        "        p.append('dead-worker request was not requeued')\n"
        "    elif getattr(t3, 'worker_id', None) == victim:\n"
        "        p.append('request credited to the dead worker')\n"
        "finally:\n"
        "    r.shutdown()\n"
        "print('FLEET ok' if not p else 'FLEET FAIL: ' + str(p[0]))")
    try:
        proc = subprocess.run([sys.executable, "-c", code],
                              capture_output=True, text=True,
                              timeout=240)
    except subprocess.TimeoutExpired:
        return False, "no response in 240s"
    lines = [ln for ln in proc.stdout.splitlines()
             if ln.startswith("FLEET")]
    if proc.returncode != 0 or not lines:
        return False, (proc.stderr.strip()[-120:]
                       or f"rc={proc.returncode}, no probe output")
    if lines[-1] != "FLEET ok":
        return False, lines[-1][:120]
    return True, ("2-worker fleet survives a kill with requeue — run "
                  "`graft_fleet` / tools/fleet_gate.py for the full "
                  "matrix")


def probe_host() -> tuple[bool, str]:
    """graft-host round-trip: spawn a 2-worker fleet split into two
    host fault domains, aim a checkpointing request at the host-1
    domain, wait for its first COMPLETE checkpoint, SIGKILL the whole
    domain, and require the host-0 survivor to requeue AND resume the
    request from the shared checkpoint rather than recompute — the
    kill-a-host contract in miniature (tools/fleet_gate.py runs the
    full 2x2 mid-batch version with bit-identity and wire-ledger
    checks).  Bounded subprocess, as for the other probes."""
    code = (
        "import os, sys, tempfile, time; sys.argv=[]; "
        "from arrow_matrix_tpu.utils.platform import "
        "force_cpu_devices; force_cpu_devices(1); "
        "import numpy as np; "
        "from arrow_matrix_tpu.fleet.router import FleetRouter; "
        "from arrow_matrix_tpu.serve.request import Request; "
        "d = tempfile.mkdtemp(prefix='host_probe_'); "
        "ck = os.path.join(d, 'ck'); "
        "r = FleetRouter(spawn=2, hosts=2, vertices=64, width=16, "
        "seed=3, run_dir=d, checkpoint_dir=ck, checkpoint_every=1); "
        "p = []; "
        "\n"
        "try:\n"
        "    hm = r.host_map()\n"
        "    if sorted(hm) != ['host-0', 'host-1']:\n"
        "        p.append('bad host map: ' + repr(hm))\n"
        "    doomed = set(hm.get('host-1') or ())\n"
        "    x = np.ones((r.n_rows, 2), dtype=np.float32)\n"
        "    ten = None\n"
        "    i = 0\n"
        "    while ten is None and i < 256:\n"
        "        if r.ring.lookup('t%d' % i) in doomed:\n"
        "            ten = 't%d' % i\n"
        "        i += 1\n"
        "    t = r.submit(Request('h0', ten, x, 32))\n"
        "    deadline = time.monotonic() + 60\n"
        "    while time.monotonic() < deadline:\n"
        "        if os.path.exists(os.path.join(ck, 'ck_h0')):\n"
        "            break\n"
        "        time.sleep(0.005)\n"
        "    else:\n"
        "        p.append('no checkpoint appeared before the kill')\n"
        "    r.kill_host('host-1')\n"
        "    r.drain(timeout_s=120)\n"
        "    if t.status != 'completed':\n"
        "        p.append('request lost with the host: '\n"
        "                 + repr((t.status, t.reason, t.error)))\n"
        "    elif getattr(t, 'requeues', 0) < 1:\n"
        "        p.append('dead-domain request was not requeued')\n"
        "    elif getattr(t, 'worker_id', None) in doomed:\n"
        "        p.append('request credited to the dead domain')\n"
        "    logs = ''\n"
        "    for h in r.workers.values():\n"
        "        if h.worker_id in doomed:\n"
        "            continue\n"
        "        try:\n"
        "            logs += open(h.log_path).read()\n"
        "        except OSError:\n"
        "            pass\n"
        "    if not p and 'resumed request' not in logs:\n"
        "        p.append('survivor recomputed instead of resuming')\n"
        "    if not p and r.live_hosts() != ['host-0']:\n"
        "        p.append('dead domain not buried: '\n"
        "                 + repr(r.live_hosts()))\n"
        "finally:\n"
        "    r.shutdown()\n"
        "print('HOST ok' if not p else 'HOST FAIL: ' + str(p[0]))")
    try:
        proc = subprocess.run([sys.executable, "-c", code],
                              capture_output=True, text=True,
                              timeout=240)
    except subprocess.TimeoutExpired:
        return False, "no response in 240s"
    lines = [ln for ln in proc.stdout.splitlines()
             if ln.startswith("HOST")]
    if proc.returncode != 0 or not lines:
        return False, (proc.stderr.strip()[-120:]
                       or f"rc={proc.returncode}, no probe output")
    if lines[-1] != "HOST ok":
        return False, lines[-1][:120]
    return True, ("kill-a-host domain survived with resume — run "
                  "tools/fleet_gate.py for quorum + bit-identity")


def probe_reshard() -> tuple[bool, str]:
    """graft-reshard round-trip: seed one mid-flight checkpoint on a
    2-device layout, grow the server onto 4 devices (the checkpoint
    replayed through a staged redistribution plan), and require the
    request to resume from the migrated checkpoint and complete — the
    kill-mid-migration contract in miniature, minus the kill
    (tools/reshard_gate.py runs the full armed version).  Bounded
    subprocess, as for the other probes."""
    code = (
        "import os, sys, tempfile; sys.argv=[]; "
        "from arrow_matrix_tpu.utils.platform import "
        "force_cpu_devices; force_cpu_devices(4); "
        "import jax; import numpy as np; "
        "from arrow_matrix_tpu.parallel.mesh import make_mesh; "
        "from arrow_matrix_tpu.serve.loadgen import "
        "ba_executor_factory, synthetic_trace; "
        "from arrow_matrix_tpu.serve.scheduler import "
        "ArrowServer, ExecConfig; "
        "from arrow_matrix_tpu.utils.checkpoint import save_state; "
        "\n"
        "d = tempfile.mkdtemp(prefix='reshard_probe_')\n"
        "devs = jax.devices()\n"
        "m2 = make_mesh((2,), ('blocks',), devices=np.asarray(devs[:2]))\n"
        "m4 = make_mesh((4,), ('blocks',), devices=np.asarray(devs))\n"
        "fac2, n_rows = ba_executor_factory(96, 16, 3, fmt='auto', "
        "mesh=m2)\n"
        "fac4, _ = ba_executor_factory(96, 16, 3, fmt='auto', mesh=m4)\n"
        "req = synthetic_trace(n_rows, tenants=1, requests=1, k=2, "
        "iterations=2, seed=7)[0]\n"
        "ex2 = fac2(ExecConfig())\n"
        "x = ex2.step(ex2.set_features(req.x))\n"
        "save_state(os.path.join(d, 'ck_' + req.request_id), "
        "np.asarray(x), 1, layout='serve/' + req.request_id "
        "+ '/k2/it2')\n"
        "srv = ArrowServer(fac2, ExecConfig(), name='probe', "
        "checkpoint_dir=d, checkpoint_every=1, max_batch_k=0, "
        "grow_factory=fac4, reshard_budget_bytes=1024)\n"
        "p = []\n"
        "if not srv.grow(reason='probe'):\n"
        "    p.append('grow refused')\n"
        "elif srv.checkpoints_resharded != 1:\n"
        "    p.append('expected 1 resharded checkpoint, got '\n"
        "             + str(srv.checkpoints_resharded))\n"
        "t = srv.submit(req)\n"
        "srv.drain()\n"
        "if t.result is None:\n"
        "    p.append('migrated request did not complete: '\n"
        "             + repr((t.status, t.error)))\n"
        "elif t.resumed_step != 1:\n"
        "    p.append('request recomputed instead of resuming the '\n"
        "             'migrated checkpoint (resumed_step='\n"
        "             + repr(t.resumed_step) + ')')\n"
        "print('RESHARD ok' if not p else 'RESHARD FAIL: ' + str(p[0]))")
    try:
        proc = subprocess.run([sys.executable, "-c", code],
                              capture_output=True, text=True,
                              timeout=240)
    except subprocess.TimeoutExpired:
        return False, "no response in 240s"
    lines = [ln for ln in proc.stdout.splitlines()
             if ln.startswith("RESHARD")]
    if proc.returncode != 0 or not lines:
        return False, (proc.stderr.strip()[-120:]
                       or f"rc={proc.returncode}, no probe output")
    if lines[-1] != "RESHARD ok":
        return False, lines[-1][:120]
    return True, ("2-dev -> 4-dev grow migrated a live checkpoint "
                  "through a staged plan and resumed it — "
                  "tools/reshard_gate.py runs the armed version")


def probe_xray() -> tuple[bool, str]:
    """graft-xray round-trip: spawn a 2-worker process fleet, route
    one request to each worker, merge the run dir into ONE fleet
    trace, and require closed span trees (each request id on the
    router track AND a worker track), a measured clock offset per
    worker that is sane for one host, and zero truncated tracks —
    the tracing loop in miniature (the SIGKILL-recovery half is
    tools/chaos_gate.py:scenario_xray_kill).  Bounded subprocess, as
    for the other probes."""
    code = (
        "import sys, tempfile; sys.argv=[]; "
        "from arrow_matrix_tpu.utils.platform import "
        "force_cpu_devices; force_cpu_devices(1); "
        "import numpy as np; "
        "from arrow_matrix_tpu.fleet.router import FleetRouter; "
        "from arrow_matrix_tpu.obs import xray; "
        "from arrow_matrix_tpu.serve.request import Request; "
        "d = tempfile.mkdtemp(prefix='xray_probe_'); "
        "r = FleetRouter(spawn=2, vertices=64, width=16, seed=3, "
        "run_dir=d); p = []; "
        "\n"
        "try:\n"
        "    x = np.ones((r.n_rows, 2), dtype=np.float32)\n"
        "    wids = sorted(r.workers)\n"
        "    ten = {}\n"
        "    i = 0\n"
        "    while len(ten) < 2 and i < 256:\n"
        "        ten.setdefault(r.ring.lookup(f't{i}'), f't{i}')\n"
        "        i += 1\n"
        "    ts = [r.submit(Request(f'p{j}', ten[w], x, 1))\n"
        "          for j, w in enumerate(wids)]\n"
        "    r.drain(timeout_s=120)\n"
        "    if not all(t.status == 'completed' for t in ts):\n"
        "        p.append('fleet warmup failed: '\n"
        "                 + repr([t.status for t in ts]))\n"
        "    report = r.fleet_summary()\n"
        "    xray.save_router_trace(r.tracer, d)\n"
        "finally:\n"
        "    r.shutdown()\n"
        "doc = xray.merge_run_dir(d, report=report)\n"
        "info = doc['xray']\n"
        "if len(info['processes']) != 3:\n"
        "    p.append('expected 3 tracks, got '\n"
        "             + repr([q['process'] for q in "
        "info['processes']]))\n"
        "if info['truncated']:\n"
        "    p.append('graceful run left truncated tracks: '\n"
        "             + repr(info['truncated']))\n"
        "offs = report.get('clock_offsets_ns') or {}\n"
        "for w in wids:\n"
        "    rec = offs.get(w)\n"
        "    if not isinstance(rec, dict):\n"
        "        p.append('no clock offset for ' + w)\n"
        "    elif abs(rec.get('offset_ns', 0)) > 1e9:\n"
        "        p.append('implausible same-host offset: ' + repr(rec))\n"
        "pid_of = {q['process']: q['pid'] for q in info['processes']}\n"
        "evs = [e for e in doc['traceEvents'] if e.get('ph') == 'X']\n"
        "for t in ts:\n"
        "    rid = t.request.request_id\n"
        "    pids = {e['pid'] for e in evs if rid in\n"
        "            str(e['args'].get('request_id', '')).split('+')}\n"
        "    if pid_of['router'] not in pids or len(pids) < 2:\n"
        "        p.append(rid + ' span tree not closed across the '\n"
        "                 'wire (pids=' + repr(sorted(pids)) + ')')\n"
        "        break\n"
        "print('XRAY ok' if not p else 'XRAY FAIL: ' + str(p[0]))")
    try:
        proc = subprocess.run([sys.executable, "-c", code],
                              capture_output=True, text=True,
                              timeout=240)
    except subprocess.TimeoutExpired:
        return False, "no response in 240s"
    lines = [ln for ln in proc.stdout.splitlines()
             if ln.startswith("XRAY")]
    if proc.returncode != 0 or not lines:
        return False, (proc.stderr.strip()[-120:]
                       or f"rc={proc.returncode}, no probe output")
    if lines[-1] != "XRAY ok":
        return False, lines[-1][:120]
    return True, ("2-worker fleet merged into one closed-span trace "
                  "with sane clock offsets — run `graft_xray report` "
                  "on any fleet run dir")


def probe_lens() -> tuple[bool, str]:
    """graft-lens round trip: profile a small BA fold level-by-level
    with the prefix-difference harness, fit the structure-conditioned
    cost model from the static counters, and predict the iteration
    back — the calibration loop in miniature.  At this smoke scale
    the tight bands the tier-1 gate enforces on the committed
    ba_256_3 point do not hold (tier times are microseconds), so the
    probe checks the round trip is structurally sound and the
    prediction lands in a loose sanity band.  Bounded subprocess, as
    for the other probes."""
    code = (
        "import sys; sys.argv=[]; "
        "from arrow_matrix_tpu.utils.platform import "
        "force_cpu_devices; force_cpu_devices(1); "
        "from arrow_matrix_tpu.obs import lens; "
        "from arrow_matrix_tpu.obs.costmodel import CostModel; "
        "from arrow_matrix_tpu.tune.search import "
        "load_levels_from_source; "
        "p = []; "
        "\n"
        "levels, width = load_levels_from_source(\n"
        "    {'kind': 'ba', 'n': 96, 'm': 3, 'width': 16,\n"
        "     'seed': 5, 'max_levels': 6})\n"
        # 200 chained steps: a step here is microseconds, and a short
        # chain's difference from the floor chain falls inside host
        # timer noise on a loaded machine (read back as the 1e-9 floor).
        "prof = lens.profile_fold(levels, width, 8, kernel='xla',\n"
        "                         feature_dtypes=('f32',), iters=200)\n"
        "ent = prof['dtypes'].get('f32') or {}\n"
        "if not ent.get('full_ms', 0.0) > 0.0:\n"
        "    p.append('no positive full-step time measured')\n"
        "tiers = ent.get('tiers') or []\n"
        "if not tiers:\n"
        "    p.append('profile attributed no tiers')\n"
        "for t in tiers:\n"
        "    for key in ('family', 'nnz', 'rows', 'streamed_bytes'):\n"
        "        if key not in t:\n"
        "            p.append('tier missing counter ' + key)\n"
        "            break\n"
        "model = lens.fit_from_profile(prof)\n"
        "if not p and not model.coeffs:\n"
        "    p.append('fit produced no per-family coefficients')\n"
        "if not p:\n"
        "    pred = lens.predict_profile_iter_ms(prof, model, 'f32')\n"
        "    full = ent['full_ms']\n"
        "    if not pred > 0.0:\n"
        "        p.append('non-positive prediction ' + repr(pred))\n"
        "    elif not 0.02 <= pred / full <= 50.0:\n"
        "        p.append('prediction insane: ' + repr(pred)\n"
        "                 + ' ms vs measured ' + repr(full) + ' ms')\n"
        "    m2 = CostModel.from_dict(model.to_dict())\n"
        "    if m2.to_dict() != model.to_dict():\n"
        "        p.append('cost model dict round trip not lossless')\n"
        "print('LENS ok' if not p else 'LENS FAIL: ' + str(p[0]))")
    # At this micro scale a host-load spike can push every tier under
    # the resolution floor (fit has no coefficients) — retry once so a
    # transient spike doesn't read as a broken calibration loop; a
    # genuinely broken fit fails both attempts.
    verdict = ""
    for _ in range(2):
        try:
            proc = subprocess.run([sys.executable, "-c", code],
                                  capture_output=True, text=True,
                                  timeout=240)
        except subprocess.TimeoutExpired:
            return False, "no response in 240s"
        lines = [ln for ln in proc.stdout.splitlines()
                 if ln.startswith("LENS")]
        if proc.returncode != 0 or not lines:
            return False, (proc.stderr.strip()[-120:]
                           or f"rc={proc.returncode}, no probe output")
        verdict = lines[-1]
        if verdict == "LENS ok":
            return True, ("per-level profile -> cost-model fit -> "
                          "prediction round trip is sane — "
                          "tools/lens_gate.py checks the committed "
                          "calibration")
    return False, verdict[:120]


def probe_synth() -> tuple[bool, str]:
    """graft-synth round trip: fingerprint a tiny BA ladder,
    synthesize the per-level schedule, certify it KC1-KC5 in
    interpret mode, persist the generated program to a throwaway
    store, and re-register + re-certify it from the store record —
    the structure-JIT loop in miniature (the raced, committed version
    is `graft_tune search --synth`; tools/kernel_gate.py checks the
    committed store).  Bounded subprocess, as for the other probes."""
    code = (
        "import sys, tempfile, os; sys.argv=[]; "
        "from arrow_matrix_tpu.utils.platform import "
        "force_cpu_devices; force_cpu_devices(1); "
        "import numpy as np; "
        "from arrow_matrix_tpu.analysis.kernels import "
        "certify_candidate_opts, certify_entry; "
        "from arrow_matrix_tpu.ops.kernel_contract import "
        "unregister_kernel; "
        "from arrow_matrix_tpu.tune import synth; "
        "from arrow_matrix_tpu.tune.fingerprint import "
        "structure_fingerprint, fingerprint_hash; "
        "from arrow_matrix_tpu.tune.search import "
        "load_levels_from_source; "
        "p = []; "
        "\n"
        "levels, width = load_levels_from_source(\n"
        "    {'kind': 'ba', 'n': 96, 'm': 3, 'width': 16,\n"
        "     'seed': 5, 'max_levels': 6})\n"
        "fp = structure_fingerprint(levels, width, np.float32)\n"
        "sched = synth.synthesize_schedule(fp)\n"
        "if not sched:\n"
        "    p.append('synthesized an empty schedule for a live ladder')\n"
        "why = certify_candidate_opts({'schedule': sched}, 16,\n"
        "                             interpret=True)\n"
        "if why is not None:\n"
        "    p.append('schedule did not certify: ' + why)\n"
        "store = os.path.join(tempfile.mkdtemp(prefix='synth_probe_'),\n"
        "                     'store.json')\n"
        "name = synth.persist_program(fp, fingerprint_hash(fp), 16,\n"
        "                             sched, path=store)\n"
        "try:\n"
        "    if name not in synth.register_persisted_programs(store):\n"
        "        p.append('store round trip lost program ' + name)\n"
        "    prog = synth.load_store(store)['programs'][name]\n"
        "    rec = certify_entry(synth.entry_from_program(name, prog))\n"
        "    if not rec['ok']:\n"
        "        p.append('stored program failed certification: '\n"
        "                 + '; '.join(rec['findings'])[:140])\n"
        "finally:\n"
        "    unregister_kernel(name)\n"
        "print('SYNTH ok ' + str(len(sched)) if not p\n"
        "      else 'SYNTH FAIL: ' + str(p[0]))")
    try:
        proc = subprocess.run([sys.executable, "-c", code],
                              capture_output=True, text=True,
                              timeout=240)
    except subprocess.TimeoutExpired:
        return False, "no response in 240s"
    lines = [ln for ln in proc.stdout.splitlines()
             if ln.startswith("SYNTH")]
    if proc.returncode != 0 or not lines:
        return False, (proc.stderr.strip()[-120:]
                       or f"rc={proc.returncode}, no probe output")
    if not lines[-1].startswith("SYNTH ok"):
        return False, lines[-1][:120]
    tiers = lines[-1].rsplit(" ", 1)[-1]
    return True, (f"{tiers}-tier schedule synthesized, certified, and "
                  f"store round-tripped — `graft_tune search --synth` "
                  f"races it for real")


def probe_native() -> tuple[bool | None, str]:
    try:
        from arrow_matrix_tpu.decomposition import native

        if not native.available():
            err = native.load_error()
            return None, ("C++ decomposer unavailable"
                          + (f" ({err})" if err else "")
                          + " — the numpy backend will be used")
        return True, "C++ decomposer built and loadable"
    except Exception as e:
        return None, f"{type(e).__name__}: {str(e)[:100]}"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--probe-timeout", type=float, default=90.0,
                    help="seconds to wait for device discovery")
    ap.add_argument("--devices", type=int, default=4,
                    help="virtual CPU pool size to verify")
    args = ap.parse_args(argv)

    ok = True
    print("arrow-matrix-tpu doctor\n")

    import importlib

    for mod in ("jax", "flax", "optax", "scipy", "numpy"):
        try:
            m = importlib.import_module(mod)
            _check(f"import {mod}", True,
                   getattr(m, "__version__", "?"))
        except ImportError as e:
            ok &= _check(f"import {mod}", False, str(e)[:100])

    acc_ok, detail = probe_accelerator(args.probe_timeout)
    _check("TPU (default backend)", True if acc_ok else None, detail)

    good, detail = probe_cpu_pool(args.devices)
    ok &= _check(f"virtual CPU pool ({args.devices} devices)", good,
                 detail)

    g, detail = probe_gloo()
    _check("multi-process collectives", g, detail)

    n, detail = probe_native()
    _check("native decomposer", n, detail)

    lint_ok, detail = probe_lint()
    ok &= _check("graft-lint (static analysis, R1-R9)", lint_ok, detail)

    prove_ok, detail = probe_prove()
    ok &= _check("graft-prove (HLO collective contracts, H1-H7)",
                 prove_ok, detail)

    sync_ok, detail = probe_sync()
    ok &= _check("graft-sync (lock discipline RC1-RC5 + witness)",
                 sync_ok, detail)

    kcert_ok, detail = probe_kcert()
    ok &= _check("graft-kcert (Pallas kernel certifier KC1-KC5)",
                 kcert_ok, detail)

    obs_ok, detail = probe_obs()
    ok &= _check("graft-scope (obs smoke trace)", obs_ok, detail)

    serve_ok, detail = probe_serve()
    ok &= _check("graft-serve (one-request round trip)", serve_ok,
                 detail)

    pulse_ok, detail = probe_pulse()
    ok &= _check("graft-pulse (endpoint scrape + schema)", pulse_ok,
                 detail)

    class_ok, detail = probe_classes()
    ok &= _check("graft-classes (certificate + approx round trip)",
                 class_ok, detail)

    tune_ok, detail = probe_tune()
    ok &= _check("graft-tune (smoke search + cache hit)", tune_ok,
                 detail)

    ledger_ok, detail = probe_ledger()
    ok &= _check("graft-ledger (record + chain + drift gate)",
                 ledger_ok, detail)

    fleet_ok, detail = probe_fleet()
    ok &= _check("graft-fleet (kill one of 2 workers + requeue)",
                 fleet_ok, detail)

    host_ok, detail = probe_host()
    ok &= _check("graft-host (kill a host domain + resume)",
                 host_ok, detail)

    reshard_ok, detail = probe_reshard()
    ok &= _check("graft-reshard (grow-migration round trip)",
                 reshard_ok, detail)

    xray_ok, detail = probe_xray()
    ok &= _check("graft-xray (merged fleet trace + clock offsets)",
                 xray_ok, detail)

    lens_ok, detail = probe_lens()
    ok &= _check("graft-lens (profile -> fit -> predict round trip)",
                 lens_ok, detail)

    synth_ok, detail = probe_synth()
    ok &= _check("graft-synth (schedule synth + certify + store)",
                 synth_ok, detail)

    cache = "bench_cache"
    if os.path.isdir(cache):
        done = [f for f in os.listdir(cache) if f.endswith(".complete")]
        _check("bench decomposition caches", True if done else None,
               f"{len(done)} cached" if done
               else "none (first bench run decomposes from scratch)")
    else:
        _check("bench decomposition caches", None,
               "no bench_cache/ (first bench run decomposes from "
               "scratch)")

    print()
    print("core checks passed" if ok else "CORE CHECKS FAILED")
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
