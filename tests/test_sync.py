"""graft-sync tests: the runtime lock-order witness (off-by-default
zero overhead, raises on inverted acquisition orders, full Condition
protocol, flock vertices in the same graph), the static RC1-RC5
analyzer (selftest twins, planted-violation fixtures per rule, the
shipped package proves clean, no drift against the checked-in
bench_cache/sync_manifest.json), regression tests for the true
findings the analyzer caught in serve//obs//fleet/, and the threaded
stress test: submit + health + pulse hammered concurrently under
AMT_LOCK_WITNESS semantics with exact pooled quantiles and a green
ledger at the end."""

import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

from arrow_matrix_tpu import sync
from arrow_matrix_tpu.analysis import sync as gsync
from arrow_matrix_tpu.fleet.health import HealthMonitor
from arrow_matrix_tpu.fleet.router import FleetRouter, WorkerHandle
from arrow_matrix_tpu.fleet.worker import FleetWorker, serve_worker
from arrow_matrix_tpu.ledger.store import Ledger
from arrow_matrix_tpu.obs.metrics import Histogram
from arrow_matrix_tpu.obs.pulse import PulseMonitor
from arrow_matrix_tpu.serve.loadgen import synthetic_trace

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURE_DIR = os.path.join(REPO, "tests", "fixtures", "sync")
MANIFEST = os.path.join(REPO, "bench_cache", "sync_manifest.json")
FIXTURES = sorted(
    os.path.join(FIXTURE_DIR, f) for f in os.listdir(FIXTURE_DIR)
    if f.startswith("rc") and f.endswith(".py"))


@pytest.fixture(autouse=True)
def _witness_restored():
    """Every test starts witness-off and leaves the global registry
    exactly as it found it (the suite must not depend on whether the
    developer exported AMT_LOCK_WITNESS)."""
    prev = sync.witness_registry()
    sync.disable_witness()
    yield
    if prev is not None:
        sync.enable_witness(prev)
    else:
        sync.disable_witness()


@pytest.fixture
def witness():
    yield sync.enable_witness()
    sync.disable_witness()


# ---------------------------------------------------------------------------
# Runtime witness
# ---------------------------------------------------------------------------

def test_witness_off_by_default_is_zero_overhead():
    # witnessed() hands back the very same lock object — not even a
    # proxy allocation — and flock regions get a shared no-op context.
    assert sync.witness_registry() is None
    lock = threading.Lock()
    assert sync.witnessed("arrow_server", lock) is lock
    cm = sync.flock_witness("sidecar")
    assert cm is sync.flock_witness("other")  # shared null
    with cm:
        pass


def test_witness_raises_on_declared_order_inversion(witness):
    la = sync.witnessed("a", threading.Lock())
    lb = sync.witnessed("b", threading.Lock())
    witness.declare("a", "b")
    with la:
        with lb:
            pass
    with lb:
        with pytest.raises(sync.LockOrderViolation, match="a"):
            la.acquire()
    snap = witness.snapshot()
    assert snap["violations"] and snap["acquisitions"] >= 3
    # The a->b traversal matched the declaration, so it is not
    # re-recorded as a new observed edge.
    assert ["a", "b"] in [list(e) for e in snap["declared_edges"]]
    assert snap["observed_edges"] == []


def test_witness_raises_on_observed_order_inversion(witness):
    # No declaration at all: the first observed order becomes law.
    lx = sync.witnessed("x", threading.Lock())
    ly = sync.witnessed("y", threading.Lock())
    with lx:
        with ly:
            pass
    with ly:
        with pytest.raises(sync.LockOrderViolation, match="observed"):
            lx.acquire()


def test_witness_reentrancy_adds_no_edge(witness):
    lr = sync.witnessed("r", threading.RLock())
    with lr:
        with lr:
            pass
    snap = witness.snapshot()
    assert snap["reentries"] == 1
    assert snap["observed_edges"] == []


def test_witness_contradictory_declaration_is_rejected():
    with pytest.raises(ValueError, match="contradicts"):
        sync.LockRegistry(declared=(("a", "b"), ("b", "a")))
    with pytest.raises(ValueError, match="self-edge"):
        sync.LockRegistry(declared=(("a", "a"),))


def test_witness_condition_protocol_round_trips(witness):
    # Condition(witnessed RLock) exercises _release_save /
    # _acquire_restore / _is_owned — a wait() must fully release the
    # witnessed stack so the notifier can acquire in order.
    lock = sync.witnessed("cond", threading.RLock())
    cond = threading.Condition(lock)
    box = {"ready": False}
    done = threading.Event()

    def waiter():
        with cond:
            while not box["ready"]:
                cond.wait(timeout=30)
        done.set()

    t = threading.Thread(target=waiter, daemon=True)
    t.start()
    with cond:
        box["ready"] = True
        cond.notify_all()
    assert done.wait(30)
    t.join(30)
    snap = witness.snapshot()
    assert snap["violations"] == []
    assert snap["acquisitions"] >= 2
    assert len(snap["threads"]) == 2


def test_flock_witness_is_a_graph_vertex(witness):
    inner = sync.witnessed("inner", threading.Lock())
    with sync.flock_witness("sidecar"):
        with inner:
            pass
    with inner:
        with pytest.raises(sync.LockOrderViolation):
            with sync.flock_witness("sidecar"):
                pass
    assert "flock:sidecar" in {a for a, _ in
                               witness.snapshot()["observed_edges"]}


def test_declared_order_matches_package_constants():
    reg = sync.LockRegistry()   # must not raise: acyclic by design
    snap = reg.snapshot()
    assert sorted(tuple(e) for e in snap["declared_edges"]) == sorted(
        sync.DECLARED_ORDER)
    assert set(sync.FLOCK_NODES) == {"flock:sidecar"}


# ---------------------------------------------------------------------------
# Static analyzer: twins, fixtures, the shipped package, the manifest
# ---------------------------------------------------------------------------

def test_analyzer_selftest_is_green():
    ok, lines = gsync.selftest()
    assert ok, "\n".join(lines)


def test_fixture_set_is_complete():
    rules = sorted(gsync.fixture_contract(p) for p in FIXTURES)
    assert rules == ["RC1", "RC2", "RC3", "RC4", "RC5"]


@pytest.mark.parametrize("path", FIXTURES,
                         ids=[os.path.basename(p) for p in FIXTURES])
def test_each_planted_fixture_fires_its_rule(path):
    ok, detail = gsync.verify_fixture(path)
    assert ok, detail
    # ...and the gate's --paths mode would reject it: any finding is
    # a nonzero exit, which is how a planted violation fails CI.
    report = gsync.analyze_paths([path])
    assert report.findings and not report.ok


def test_sync_gate_cli_rejects_planted_fixtures():
    """The actual tools/sync_gate.py process exits nonzero when fed
    the planted violations, naming every rule."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "sync_gate.py"),
         "--paths", *FIXTURES],
        capture_output=True, text=True, timeout=300, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0, proc.stdout + proc.stderr
    for rule in ("RC1", "RC2", "RC3", "RC4", "RC5"):
        assert rule in proc.stdout, (rule, proc.stdout)


def test_shipped_package_proves_clean():
    report = gsync.analyze_package()
    assert report.findings == [], "\n".join(
        f.format() for f in report.findings)
    assert report.ok
    nodes = {c.node for c in report.contracts}
    assert {"arrow_server", "fleet_router", "health_monitor",
            "pulse_monitor", "slo_watchdog", "flight_recorder",
            "metrics_registry", "hbm_accountant"} <= nodes


def test_manifest_checked_in_ok_and_no_drift():
    with open(MANIFEST, encoding="utf-8") as fh:
        checked_in = json.load(fh)
    assert checked_in["ok"], "checked-in sync manifest records findings"
    fresh = gsync.run_sync(write=False)
    drift = gsync.manifest_drift(checked_in, fresh)
    assert drift == [], "\n".join(drift)


# ---------------------------------------------------------------------------
# Regressions for the true findings graft-sync caught
# ---------------------------------------------------------------------------

def test_health_racing_failures_each_count():
    """The HealthMonitor lost-update fix: N racing record_failure
    calls must produce a streak of exactly N (two racing threads used
    to each observe N-1 and neither bury the worker)."""
    hm = HealthMonitor(timeout_s=1.0, max_failures=10**6)
    threads = [threading.Thread(
        target=lambda: [hm.record_failure("w", "boom")
                        for _ in range(250)])
        for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    assert hm.snapshot()["w"]["consecutive_failures"] == 8 * 250


def test_pulse_hbm_sampler_runs_before_the_monitor_lock(witness):
    """The RC3 fix in PulseMonitor.observe: the sampler (a user
    callback that takes other locks — here the declared-higher
    arrow_server lock) must run BEFORE the pulse lock is taken.  If it
    ran under the lock, acquiring arrow_server inside pulse_monitor
    would close the declared arrow_server -> pulse_monitor cycle and
    the witness would raise."""
    server_lock = sync.witnessed("arrow_server", threading.Lock())

    def sampler():
        with server_lock:
            return (1 << 20, 0.5)

    m = PulseMonitor(window_s=10.0, hbm_sampler=sampler)
    for _ in range(4):
        m.observe("completed", latency_ms=1.0)
    snap = witness.snapshot()
    assert snap["violations"] == []
    assert m.totals_dict()["completed"] == 4


def test_pulse_concurrent_observe_never_drops_events():
    """The RC1 fix (burn_events/totals folded under the lock): T
    threads hammering observe() concurrently lose nothing."""
    m = PulseMonitor(window_s=0.01)
    per_thread = 300

    def hammer(tid):
        for i in range(per_thread):
            m.observe("completed", tenant=f"t{tid}",
                      latency_ms=float(i % 7))
            if i % 50 == 0:
                m.advance()

    threads = [threading.Thread(target=hammer, args=(tid,))
               for tid in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    # merged_latency pools closed windows + the in-progress one, so
    # sample it before close() seals the final window into the ring.
    assert len(m.merged_latency().values) == 6 * per_thread
    m.close()
    assert m.totals_dict()["completed"] == 6 * per_thread


# ---------------------------------------------------------------------------
# The threaded stress test (satellite): fleet + health + pulse under
# the witness, exact quantiles and a green ledger at the end.
# ---------------------------------------------------------------------------

def _start_worker(worker_id, checkpoint_dir):
    worker = FleetWorker(worker_id, vertices=64, width=16, seed=5,
                         checkpoint_dir=checkpoint_dir,
                         checkpoint_every=1)
    ready = threading.Event()
    box = {}

    def announce(port):
        box["port"] = port
        ready.set()

    th = threading.Thread(target=serve_worker, args=(worker,),
                          kwargs={"port": 0, "announce": announce},
                          daemon=True)
    th.start()
    assert ready.wait(120), f"{worker_id} never bound"
    return worker, WorkerHandle(worker_id, "127.0.0.1", box["port"])


def test_threaded_stress_under_witness(tmp_path):
    """N threads hammer FleetRouter.submit, the HealthMonitor's
    ok/failure transitions, and PulseMonitor.observe simultaneously
    with the lock-order witness armed.  Every request completes, the
    fleet quantiles are still EXACTLY the pooled nearest-rank over the
    workers' raw samples, the pulse ledger validates clean, and the
    witness saw a multi-threaded run with zero order violations."""
    registry = sync.enable_witness()
    ledger_dir = str(tmp_path / "ledger")
    ckpt = str(tmp_path / "ckpt")
    w0, h0 = _start_worker("w0", ckpt)
    w1, h1 = _start_worker("w1", ckpt)
    router = FleetRouter(
        handles=[h0, h1],
        health=HealthMonitor(timeout_s=5.0, max_failures=3))
    pm = PulseMonitor(window_s=0.02, ledger_dir=ledger_dir)
    tickets = []
    tickets_lock = threading.Lock()
    try:
        trace = synthetic_trace(router.n_rows, tenants=4, requests=12,
                                k=2, iterations=1, seed=7)
        chunks = [trace[i::3] for i in range(3)]

        def submitter(chunk):
            for req in chunk:
                t = router.submit(req)
                with tickets_lock:
                    tickets.append(t)

        def health_flapper():
            # Sub-lethal failure streaks interleaved with oks and
            # snapshots: the burial read-modify-write races against
            # every dispatch thread's record_ok.
            for _ in range(150):
                router.health.record_failure("w0", "flap")
                router.health.record_ok("w0")
                router.health.snapshot()
                router.live_workers()

        def pulser(tid):
            for i in range(200):
                pm.observe("completed", tenant=f"t{tid % 4}",
                           latency_ms=float(i % 11))
                if i % 40 == 0:
                    pm.advance()

        threads = ([threading.Thread(target=submitter, args=(c,))
                    for c in chunks]
                   + [threading.Thread(target=health_flapper)]
                   + [threading.Thread(target=pulser, args=(tid,))
                      for tid in range(3)])
        for t in threads:
            t.start()
        for t in threads:
            t.join(180)
        router.drain(timeout_s=180)

        assert [t.status for t in tickets] == ["completed"] * 12

        report = router.fleet_summary()
        assert report["completed"] == 12
        assert report["failed"] == 0 and report["shed"] == 0
        pooled = Histogram()
        for rec in report["workers"].values():
            for v in rec.get("latency_samples_ms") or ():
                pooled.observe(v)
        lat = report["latency_ms"]
        assert lat["count"] == len(pooled.values) == 12
        for q, field in ((0.5, "p50"), (0.9, "p90"), (0.99, "p99")):
            assert lat[field] == pooled.quantile(q)

        pm.close()
        assert pm.totals_dict()["completed"] == 3 * 200
        assert Ledger(ledger_dir).validate() == []

        snap = registry.snapshot()
        assert snap["violations"] == [], "\n".join(snap["violations"])
        assert snap["acquisitions"] > 0
        assert len(snap["threads"]) >= 4
    finally:
        sync.disable_witness()
        router.shutdown()
        for w in (w0, w1):
            try:
                w.close()
            except Exception:
                pass
