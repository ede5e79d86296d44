"""graft-serve unit tests: the shared RetryPolicy (deterministic
seeded backoff jitter), HBM admission-control edge cases
(exactly-at-budget, zero-headroom, burst shedding — all with
deterministic censuses), dynamic feature-axis batching bit-identity,
the graceful-degradation ladder, legacy-checkpoint resume events, and
the deterministic load generator / SLO report.  The full chaos-under-
load matrix lives in tools/serve_gate.py (wired into the chaos-gate
tests in test_faults.py)."""

import argparse
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from arrow_matrix_tpu import faults
from arrow_matrix_tpu.faults import RetryPolicy, Supervisor
from arrow_matrix_tpu.obs import flight
from arrow_matrix_tpu.serve import (
    ArrowServer,
    ExecConfig,
    HBMAccountant,
    ba_executor_factory,
    degradation_ladder,
    request_price_bytes,
    run_trace,
    slo_summary,
    smoke_serve,
    synthetic_trace,
)

N, WIDTH, K, SEED = 64, 16, 2, 5


@pytest.fixture(autouse=True)
def _no_leftover_plan():
    faults.clear_plan()
    yield
    faults.clear_plan()


@pytest.fixture(scope="module")
def factory():
    """One BA decomposition shared by every server in this module."""
    return ba_executor_factory(N, WIDTH, SEED, fmt="fold")


def _serve(factory_pair, trace, **kw):
    fac, n_rows = factory_pair
    base = kw.pop("base_config", ExecConfig())
    srv = ArrowServer(fac, base, policy=RetryPolicy(backoff_s=0.001),
                      **kw)
    return srv, run_trace(srv, trace)


def _trace(n_rows, requests=4, tenants=2, iterations=2, **kw):
    return synthetic_trace(n_rows, tenants=tenants, requests=requests,
                           k=K, iterations=iterations, seed=SEED, **kw)


# ---------------------------------------------------------------------------
# RetryPolicy (faults/policy.py)
# ---------------------------------------------------------------------------

def test_policy_jitter_deterministic():
    p = RetryPolicy(max_retries=3, backoff_s=0.1, jitter=0.5, seed=7)
    assert p.delay_s(1, salt="a") == p.delay_s(1, salt="a")
    assert p.schedule(salt="a") == p.schedule(salt="a")
    # Different salts / attempts draw different jitter.
    assert p.delay_s(1, salt="a") != p.delay_s(1, salt="b")
    # Jitter stays within the +/- fraction around the exponential base.
    for a in (1, 2, 3):
        base = 0.1 * 2.0 ** (a - 1)
        assert abs(p.delay_s(a, salt="x") - base) <= 0.5 * base + 1e-12


def test_policy_no_jitter_is_pure_exponential():
    p = RetryPolicy(max_retries=3, backoff_s=0.05, backoff_factor=3.0)
    assert p.schedule() == pytest.approx((0.05, 0.15, 0.45))


def test_policy_validation():
    with pytest.raises(ValueError, match="max_retries"):
        RetryPolicy(max_retries=-1)
    with pytest.raises(ValueError, match="jitter"):
        RetryPolicy(jitter=1.5)
    with pytest.raises(ValueError, match="backoff"):
        RetryPolicy(backoff_factor=0.5)


def test_policy_from_args_reads_heal_flags():
    ns = argparse.Namespace(max_retries=5, watchdog=2.0,
                            retry_jitter=0.25, seed=9)
    p = RetryPolicy.from_args(ns)
    assert (p.max_retries, p.watchdog_s, p.jitter, p.seed) == \
        (5, 2.0, 0.25, 9)
    q = RetryPolicy.from_args(ns, max_retries=1)
    assert q.max_retries == 1   # explicit override wins


def test_supervisor_legacy_kwargs_and_policy():
    sup = Supervisor("t", max_retries=5, backoff_s=0.5, verbose=False)
    assert sup.policy.max_retries == 5 and sup.max_retries == 5
    pol = RetryPolicy(max_retries=1, watchdog_s=3.0)
    sup2 = Supervisor("t", max_retries=9, policy=pol, verbose=False)
    assert sup2.max_retries == 1 and sup2.watchdog_s == 3.0


# ---------------------------------------------------------------------------
# HBM accountant + admission edge cases
# ---------------------------------------------------------------------------

def test_accountant_exact_at_budget():
    acc = HBMAccountant(100)
    acc.charge_resident(40)
    assert acc.reserve(60)          # exactly at budget admits
    assert not acc.reserve(1)       # one byte over is rejected
    acc.release(60)
    assert acc.headroom_bytes() == 60
    acc.release(10 ** 9)            # release floors at resident
    snap = acc.snapshot()
    assert snap["in_use_bytes"] == 40 == snap["resident_bytes"]
    assert snap["peak_in_use_bytes"] == 100


def test_accountant_rejects_resident_over_budget():
    from arrow_matrix_tpu.serve import ServeCapacityError

    acc = HBMAccountant(10)
    with pytest.raises(ServeCapacityError):
        acc.charge_resident(11)


def test_admission_exactly_at_budget(factory):
    """A budget with headroom for exactly one request admits exactly
    one (<=, not <) and explicitly rejects the second."""
    fac, n_rows = factory
    from arrow_matrix_tpu.obs.memview import predicted_bytes_for

    ex = fac(ExecConfig())
    resident = predicted_bytes_for(ex, 0) or 0
    price = request_price_bytes(ex, K)
    assert price > 0
    srv, tickets = _serve(factory, _trace(n_rows, requests=2),
                          hbm_budget_bytes=resident + price)
    s = srv.summary()
    assert (s["admitted"], s["rejected"], s["completed"]) == (1, 1, 1)
    assert tickets[0].status == "completed"
    assert tickets[1].status == "rejected"
    assert tickets[1].reason == "hbm_budget"
    assert "headroom" in tickets[1].error


def test_admission_zero_headroom_budget(factory):
    """A budget covering only the resident operator rejects every
    request — deterministically, never silently."""
    fac, n_rows = factory
    from arrow_matrix_tpu.obs.memview import predicted_bytes_for

    resident = predicted_bytes_for(fac(ExecConfig()), 0) or 0
    srv, tickets = _serve(factory, _trace(n_rows, requests=3),
                          hbm_budget_bytes=resident)
    s = srv.summary()
    assert (s["admitted"], s["rejected"]) == (0, 3)
    assert all(t.status == "rejected" and t.reason == "hbm_budget"
               for t in tickets)
    assert s["hbm"]["peak_in_use_bytes"] <= resident


def test_burst_shedding_deterministic(factory):
    """A burst past the bounded queue sheds exactly the overflow with
    an explicit reason, and the census replays identically."""
    fac, n_rows = factory

    def burst():
        srv = ArrowServer(fac, ExecConfig(), queue_capacity=2,
                          policy=RetryPolicy(backoff_s=0.001))
        tickets = [srv.submit(r)
                   for r in _trace(n_rows, requests=6)]   # no draining
        srv.drain()
        return srv.summary(), [(t.status, t.reason) for t in tickets]

    s1, census1 = burst()
    s2, census2 = burst()
    assert (s1["completed"], s1["shed"]) == (2, 4)
    assert census1 == census2
    assert census1.count(("shed", "queue_full")) == 4
    assert all(status in ("completed", "shed") for status, _ in census1)


def test_deadline_expired_requests_shed_explicitly(factory):
    fac, n_rows = factory
    srv, tickets = _serve(factory,
                          _trace(n_rows, requests=2, deadline_s=1e-9))
    assert all(t.status == "shed" and t.reason == "deadline"
               for t in tickets)
    assert srv.summary()["shed"] == 2


def test_submit_after_shutdown_sheds_explicitly(factory):
    fac, n_rows = factory
    srv = ArrowServer(fac, ExecConfig(), queue_capacity=4)
    srv.start()
    srv.shutdown(wait=True)
    t = srv.submit(_trace(n_rows, requests=1)[0])
    assert t.status == "shed" and t.reason == "server_stopped"


# ---------------------------------------------------------------------------
# Dynamic batching + worker-thread mode
# ---------------------------------------------------------------------------

def test_batching_bit_identical(factory):
    """Feature-axis batching (SpMM column separability) returns each
    request exactly the bytes it gets when run alone."""
    fac, n_rows = factory
    trace = _trace(n_rows, requests=4)
    solo_srv, solo = _serve(factory, trace)
    trace2 = _trace(n_rows, requests=4)
    batched_srv, batched = _serve(factory, trace2, max_batch_k=4 * K)
    assert solo_srv.batches == 4
    assert batched_srv.batches < 4
    assert batched_srv.batched_requests == 4
    for a, b in zip(solo, batched):
        assert a.status == b.status == "completed"
        assert a.result.tobytes() == b.result.tobytes()


def test_worker_thread_mode_completes(factory):
    fac, n_rows = factory
    srv = ArrowServer(fac, ExecConfig(), queue_capacity=8,
                      policy=RetryPolicy(backoff_s=0.001))
    srv.start()
    try:
        tickets = run_trace(srv, _trace(n_rows, requests=3))
    finally:
        srv.shutdown(wait=True)
    assert all(t.status == "completed" for t in tickets)


# ---------------------------------------------------------------------------
# Graceful degradation
# ---------------------------------------------------------------------------

def test_degradation_ladder_order():
    ladder = degradation_ladder(
        ExecConfig(kernel="pallas_sell", overlap_slabs=2))
    assert [(c.kernel, c.overlap_slabs) for c in ladder] == [
        ("pallas_sell", 2), ("xla", 2), ("xla", 1)]
    assert degradation_ladder(ExecConfig()) == (ExecConfig(),)
    assert not hasattr(ExecConfig(), "repl")


def test_exec_config_divisibility():
    cfg = ExecConfig(overlap_slabs=2)
    assert cfg.accepts_k(4) and cfg.accepts_k(2)
    assert not cfg.accepts_k(3)   # S=2 does not divide 3
    assert not cfg.accepts_k(0)
    assert ExecConfig().accepts_k(1)


def test_repeated_faults_degrade_then_complete(factory):
    """Retries exhausted on the base rung: the tenant degrades one
    rung (pallas_sell -> xla, the one-chip fold's rung) and the
    request completes there, bit-identical — only a terminal-rung
    tenant can fail."""
    fac, n_rows = factory
    trace = _trace(n_rows, requests=1, tenants=1)
    _, ref = _serve(factory, _trace(n_rows, requests=1, tenants=1),
                    base_config=ExecConfig(kernel="pallas_sell"))
    faults.set_plan({"scenario": "error", "site": "multi_level.step",
                     "after": 0, "count": 2})
    srv = ArrowServer(fac, ExecConfig(kernel="pallas_sell"),
                      policy=RetryPolicy(max_retries=1,
                                         backoff_s=0.001),
                      degrade_after=1)
    tickets = run_trace(srv, trace)
    faults.clear_plan()
    s = srv.summary()
    assert tickets[0].status == "completed"
    assert s["faults_seen"] >= 2
    tenant = s["tenants"][trace[0].tenant]
    assert tenant["rung"] == 1
    assert tenant["config"] == {"kernel": "xla", "overlap_slabs": 1,
                                "feature_dtype": None}
    assert tenant["degradations"]
    assert tickets[0].result.tobytes() == ref[0].result.tobytes()
    assert tickets[0].attempts == 2


# ---------------------------------------------------------------------------
# Legacy checkpoint resume (satellite: resumed event + loud warning)
# ---------------------------------------------------------------------------

def test_legacy_checkpoint_resume_warns_and_events(tmp_path, capsys):
    """A pre-version (untagged) checkpoint loads with a LOUD warning
    and a ``resumed`` flight event carrying the supervisor's name and
    ``legacy=True`` — never a crash."""
    ck = str(tmp_path / "legacy_ck")
    like = jnp.zeros((8, 2), dtype=jnp.float32)
    x_mid = np.arange(16, dtype=np.float32).reshape(8, 2)
    np.savez(ck + ".npz", x=x_mid, step=np.int64(3))   # no version tag
    rec = flight.FlightRecorder(str(tmp_path / "flight.json"))
    flight.set_recorder(rec)
    try:
        sup = Supervisor("req-legacy", checkpoint_path=ck,
                         layout="serve/test", verbose=False)
        state = sup.resume(like)
    finally:
        flight.set_recorder(None)
    assert state is not None and state[1] == 3
    assert np.asarray(state[0]).tobytes() == x_mid.tobytes()
    err = capsys.readouterr().err
    assert "WARNING" in err and "legacy" in err
    resumed = [e["data"] for e in rec.events
               if e.get("name") == "resumed"
               and e.get("data", {}).get("supervisor") == "req-legacy"]
    assert resumed and resumed[-1]["legacy"] is True
    assert resumed[-1]["step"] == 3


def test_tagged_checkpoint_resume_not_legacy(tmp_path):
    from arrow_matrix_tpu.utils.checkpoint import checkpoint_meta

    ck = str(tmp_path / "tagged_ck")
    like = jnp.ones((4, 2), dtype=jnp.float32)
    sup = Supervisor("req-tagged", checkpoint_path=ck,
                     layout="serve/test", verbose=False)
    sup._save(like, 2)
    meta = checkpoint_meta(ck)
    assert meta is not None and meta["version"] >= 1
    rec = flight.FlightRecorder(str(tmp_path / "flight.json"))
    flight.set_recorder(rec)
    try:
        state = sup.resume(like)
    finally:
        flight.set_recorder(None)
    assert state is not None and state[1] == 2
    resumed = [e["data"] for e in rec.events
               if e.get("name") == "resumed"
               and e.get("data", {}).get("supervisor") == "req-tagged"]
    assert resumed and resumed[-1]["legacy"] is False


# ---------------------------------------------------------------------------
# Load generator + SLO report
# ---------------------------------------------------------------------------

def test_synthetic_trace_deterministic():
    a = synthetic_trace(32, tenants=3, requests=5, k=2, seed=4)
    b = synthetic_trace(32, tenants=3, requests=5, k=2, seed=4)
    assert [r.tenant for r in a] == [r.tenant for r in b]
    assert all(x.x.tobytes() == y.x.tobytes() for x, y in zip(a, b))
    c = synthetic_trace(32, tenants=3, requests=5, k=2, seed=5)
    assert any(x.x.tobytes() != y.x.tobytes() for x, y in zip(a, c))


def test_slo_summary_and_artifacts(factory, tmp_path):
    fac, n_rows = factory
    srv, tickets = _serve(factory, _trace(n_rows, requests=3))
    summary = slo_summary(srv, tickets, wall_s=1.0)
    lat = summary["latency_ms"]
    assert summary["completed"] == 3
    assert lat["p50"] is not None and lat["p99"] is not None
    assert lat["p50"] <= lat["p99"]
    assert summary["requests_per_s"] == 3.0
    for field in ("shed", "rejected", "hbm", "per_tenant",
                  "faults_seen", "checkpoint_corruptions"):
        assert field in summary
    for rec in summary["per_tenant"].values():
        assert "latency_ms" in rec and "rung" in rec
    from arrow_matrix_tpu.serve import write_serve_artifacts

    path = write_serve_artifacts(str(tmp_path), summary)
    with open(path, encoding="utf-8") as fh:
        assert json.load(fh)["completed"] == 3


def test_smoke_serve_round_trip(tmp_path):
    out = str(tmp_path / "serve")
    s = smoke_serve(out, n=N, width=WIDTH)
    assert s["completed"] == s["requests"] and s["failed"] == 0
    assert s["latency_ms"]["p99"] is not None
    assert os.path.isfile(os.path.join(out, "serve_summary.json"))
    assert os.path.isfile(os.path.join(out, "metrics.jsonl"))


def test_request_price_matches_carriage_model(factory):
    fac, _ = factory
    ex = fac(ExecConfig())
    price = request_price_bytes(ex, K)
    assert price == ex.carriage_hbm_bytes(K)
    assert price > 0
    assert ex.predicted_hbm_bytes(K) - ex.predicted_hbm_bytes(0) == price
