"""Deterministic load generation + SLO reporting for graft-serve.

``synthetic_trace`` derives every request — tenant assignment and
feature payload — from ``numpy.random.default_rng(seed)``: no
wall-clock randomness anywhere, so two runs of the same trace through
a fault-free server complete with bit-identical per-request results
and identical admission censuses (the replay property every chaos
scenario in tools/serve_gate.py compares against).

``slo_summary`` folds the server's census and the tickets' latencies
into the serving SLO report (requests/s, p50/p90/p99 latency, shed and
rejection counts, HBM occupancy, per-tenant breakdown) —
tools/obs_gate.py requires these fields in every serve report, and
PERFORMANCE.md's serving table is this dict verbatim.

**One schema with graft-pulse.**  The report's field names are the
same vocabulary the streaming time series uses
(``obs/pulse.py:SLO_SERIES_FIELDS`` / ``LATENCY_FIELDS``):
``completed``/``failed``/``shed``/``rejected``, ``requests_per_s``,
``latency_ms{count,p50,p90,p99,mean,max}``, ``hbm``, ``per_tenant``.
A summary built with ``pulse=`` additionally embeds the monitor's
closed-window series under ``"pulse"``, so a replay artifact carries
both the end-state report and the time-resolved path to it, and the
two can be diffed field-for-field (the obs gate asserts the pooled
window histograms match the report's quantiles).
"""

from __future__ import annotations

import os
import time
from typing import Dict, List, Optional

import numpy as np

from arrow_matrix_tpu.ledger import store as ledger_store
from arrow_matrix_tpu.serve import request as rq
from arrow_matrix_tpu.serve.scheduler import ArrowServer, ExecConfig
from arrow_matrix_tpu.utils.artifacts import atomic_write_json


def synthetic_trace(n_rows: int, *, tenants: int = 4,
                    requests: int = 16, k: int = 4,
                    iterations: int = 3, seed: int = 0,
                    deadline_s: Optional[float] = None
                    ) -> List[rq.Request]:
    """A reproducible heavy-traffic trace: ``requests`` requests from
    ``tenants`` synthetic tenants, feature payloads and tenant
    assignment both drawn from one seeded generator."""
    rng = np.random.default_rng(seed)
    out = []
    for i in range(requests):
        tenant = f"tenant{int(rng.integers(tenants))}"
        x = rng.standard_normal((n_rows, k)).astype(np.float32)
        out.append(rq.Request(request_id=f"r{i:04d}", tenant=tenant,
                              x=x, iterations=iterations,
                              deadline_s=deadline_s))
    return out


def run_trace(server: ArrowServer,
              trace: List[rq.Request]) -> List[rq.Ticket]:
    """Submit the whole trace, then drain synchronously (or, when the
    server's worker thread is running, wait for every ticket) —
    returns the tickets in trace order."""
    tickets = [server.submit(r) for r in trace]
    if server._thread is not None and server._thread.is_alive():
        for t in tickets:
            t.wait()
    else:
        server.drain()
    return tickets


def _pct(vals: List[float], q: float) -> Optional[float]:
    if not vals:
        return None
    s = sorted(vals)
    return s[min(len(s) - 1, int(round(q * (len(s) - 1))))]


def latency_summary_ms(tickets: List[rq.Ticket]) -> Dict[str, float]:
    lats = [t.latency_s * 1e3 for t in tickets
            if t.status == rq.COMPLETED and t.latency_s is not None]
    if not lats:
        return {"count": 0, "p50": None, "p90": None, "p99": None,
                "mean": None, "max": None}
    return {"count": len(lats),
            "p50": _pct(lats, 0.5), "p90": _pct(lats, 0.9),
            "p99": _pct(lats, 0.99),
            "mean": sum(lats) / len(lats), "max": max(lats)}


def slo_summary(server: ArrowServer, tickets: List[rq.Ticket],
                wall_s: float, pulse=None) -> dict:
    """The serving SLO report tools/obs_gate.py validates; pass the
    run's :class:`~arrow_matrix_tpu.obs.pulse.PulseMonitor` to embed
    its windowed time series (one schema, see the module docstring)."""
    base = server.summary()
    per_tenant = {}
    for name, rec in base["tenants"].items():
        mine = [t for t in tickets if t.request.tenant == name]
        rec = dict(rec)
        rec["latency_ms"] = latency_summary_ms(mine)
        per_tenant[name] = rec
    # graft-classes: the per-class mirror of per_tenant — latency
    # quantiles keyed by the class actually served (post-fallback), so
    # an SLO read can tell approx tail latency from exact.
    per_class = {}
    for klass, rec in (base.get("classes") or {}).items():
        mine = [t for t in tickets if t.served_class == klass]
        rec = dict(rec)
        rec["latency_ms"] = latency_summary_ms(mine)
        per_class[klass] = rec
    completed = base["completed"]
    pulse_section = None
    if pulse is not None:
        pulse_section = {
            "window_s": pulse.window_s,
            "windows": pulse.series(),
            "totals": pulse.totals_dict(),
            "burn_events": list(pulse.burn_events),
            "dropped_windows": pulse.dropped_windows,
            "ring_path": pulse.ring_path,
        }
    return {
        "server": base["server"],
        "requests": len(tickets),
        "completed": completed,
        "failed": base["failed"],
        "shed": base["shed"],
        "rejected": base["rejected"],
        "wall_s": wall_s,
        "requests_per_s": (completed / wall_s) if wall_s > 0 else None,
        "latency_ms": latency_summary_ms(tickets),
        "hbm": base["hbm"],
        "batches": base["batches"],
        "batched_requests": base["batched_requests"],
        "faults_seen": base["faults_seen"],
        "recoveries": base["recoveries"],
        "checkpoint_corruptions": base["checkpoint_corruptions"],
        "per_tenant": per_tenant,
        "per_class": per_class,
        "class_fallback": base.get("class_fallback", 0),
        "certificates": base.get("certificates", {}),
        "pulse": pulse_section,
    }


def write_serve_artifacts(run_dir: str, summary: dict,
                          registry=None) -> str:
    """Persist ``serve_summary.json`` (+ the registry's
    ``metrics.jsonl``) under ``run_dir``; returns the summary path."""
    os.makedirs(run_dir, exist_ok=True)
    path = os.path.join(run_dir, "serve_summary.json")
    atomic_write_json(path, summary, indent=2, sort_keys=True)
    if registry is not None:
        registry.write_jsonl(os.path.join(run_dir, "metrics.jsonl"))
    return path


def ba_executor_factory(n: int, width: int, seed: int,
                        fmt: str = "fold", mesh=None,
                        feature_dtype=None, plan=None,
                        plan_k=None):
    """Factory-of-executors over one Barabasi-Albert decomposition:
    the decomposition is computed once (the resident operator), each
    :class:`ExecConfig` rung builds its own executor over the same
    levels.  Returns ``(factory, n_rows)``.

    ``plan`` (graft-tune) threads into every rung's build: the rung's
    ExecConfig still wins on kernel/overlap — the degradation
    ladder must be able to step a tuned knob down — while the plan
    contributes the structural knobs (tier split, chunk, carriage
    dtype) and the fused-kernel call opts."""
    from arrow_matrix_tpu.decomposition import arrow_decomposition
    from arrow_matrix_tpu.utils import barabasi_albert

    a = barabasi_albert(n, 3, seed=seed)
    levels = arrow_decomposition(a, width, max_levels=10,
                                 block_diagonal=True, seed=seed)

    resolved = None
    if plan is not None:
        from arrow_matrix_tpu.tune.plan import resolve_plan

        resolved = resolve_plan(plan, levels=levels, width=width,
                                plan_k=plan_k)

    def factory(cfg: ExecConfig):
        from arrow_matrix_tpu.parallel import MultiLevelArrow

        kwargs = dict(fmt=fmt, feature_dtype=feature_dtype)
        kernel_opts = None
        if resolved is not None:
            bk = resolved.build_kwargs()
            kwargs.update(fmt=bk["fmt"], chunk=bk["chunk"],
                          fold_growth=bk["fold_growth"],
                          fold_align=bk["fold_align"],
                          feature_dtype=bk["feature_dtype"])
            kernel_opts = resolved.kernel_opts()
        # graft-classes: the rung's class carriage wins over both the
        # factory default and the plan — an approx batch must build a
        # reduced-precision executor even under an exact-tuned plan.
        if getattr(cfg, "feature_dtype", None) is not None:
            kwargs["feature_dtype"] = cfg.feature_dtype
        return MultiLevelArrow(levels, width, mesh=mesh,
                               kernel=cfg.kernel,
                               overlap_slabs=cfg.overlap_slabs,
                               kernel_opts=kernel_opts,
                               **kwargs)

    return factory, n


def smoke_serve(run_dir: str, *, n: int = 96, width: int = 16,
                k: int = 2, tenants: int = 2, requests: int = 4,
                iterations: int = 2, seed: int = 3,
                queue_capacity: int = 8,
                hbm_budget_bytes: Optional[int] = None,
                max_batch_k: int = 0, registry=None) -> dict:
    """One tiny end-to-end serve run on the host-CPU backend: build a
    BA operator, serve a deterministic trace with a PulseMonitor
    attached, write the SLO artifacts (``serve_summary.json``,
    ``pulse_ring.json``, ``pulse_metrics.prom``) into ``run_dir``,
    return the summary.  The amt_doctor SERVE probe and
    tools/obs_gate.py both ride this."""
    from arrow_matrix_tpu.obs import pulse as pulse_mod

    if registry is None:
        from arrow_matrix_tpu.obs.metrics import MetricsRegistry

        registry = MetricsRegistry(run_dir=run_dir)
    os.makedirs(run_dir, exist_ok=True)
    factory, n_rows = ba_executor_factory(n, width, seed, fmt="fold")
    server = ArrowServer(factory, ExecConfig(),
                         hbm_budget_bytes=hbm_budget_bytes,
                         queue_capacity=queue_capacity,
                         max_batch_k=max_batch_k,
                         registry=registry, name="smoke")
    monitor = pulse_mod.PulseMonitor(
        window_s=0.25, name="smoke",
        ring_path=os.path.join(run_dir, "pulse_ring.json"),
        ledger_dir=os.path.join(run_dir, "ledger"),
        watchdog=pulse_mod.SloWatchdog())
    server.attach_pulse(monitor)
    trace = synthetic_trace(n_rows, tenants=tenants,
                            requests=requests, k=k,
                            iterations=iterations, seed=seed)
    t0 = time.perf_counter()
    tickets = run_trace(server, trace)
    wall = time.perf_counter() - t0
    monitor.close()
    with open(os.path.join(run_dir, "pulse_metrics.prom"), "w",
              encoding="utf-8") as fh:
        fh.write(monitor.exposition_text())
    summary = slo_summary(server, tickets, wall, pulse=monitor)
    # graft-ledger: the SLO report also lands in a RUN-DIR-LOCAL
    # store (smoke runs ride gates and tests; they must never append
    # to the committed ledger).  tools/obs_gate.py requires the id.
    rec = ledger_store.record(
        "serve", "requests_per_s", summary.get("requests_per_s"),
        directory=os.path.join(run_dir, "ledger"),
        unit="req/s",
        knobs={"n": n, "width": width, "k": k, "seed": seed,
               "tenants": tenants, "requests": requests,
               "iterations": iterations,
               "max_batch_k": max_batch_k},
        payload={key: summary[key] for key in
                 ("requests", "completed", "failed", "shed",
                  "rejected", "wall_s", "latency_ms", "batches",
                  "batched_requests") if key in summary})
    summary["ledger_record_id"] = rec["record_id"] if rec else None
    write_serve_artifacts(run_dir, summary, registry=registry)
    return summary
