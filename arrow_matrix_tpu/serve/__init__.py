"""graft-serve: the always-on multi-tenant SpMM serving runtime.

ROADMAP item 1's pivot from batch to serving: the decomposed arrow
operator stays HBM-resident while a stream of concurrent tenant
requests runs over it, each under graft-heal supervision.  The pieces:

  * :mod:`~arrow_matrix_tpu.serve.request` — the request/ticket model
    (every request reaches exactly one explicit terminal state).
  * :mod:`~arrow_matrix_tpu.serve.admission` — the live HBM
    accountant; requests are priced via the memview static model
    *before* enqueue and rejected 429-style when over budget.
  * :mod:`~arrow_matrix_tpu.serve.scheduler` — bounded queue +
    deterministic FIFO scheduler with dynamic feature-axis batching,
    per-request Supervisor (watchdog / seeded-backoff retry /
    sha256-verified checkpoint resume), and the graceful-degradation
    ladder pallas_sell -> xla, overlap S -> 1.
  * :mod:`~arrow_matrix_tpu.serve.loadgen` — deterministic synthetic
    traces and the SLO report (requests/s, p50/p99, shed counts, HBM
    occupancy) obs_gate validates — one field vocabulary with the
    graft-pulse streaming series (obs/pulse.py), which attaches to a
    server via ``ArrowServer.attach_pulse`` for live windowed
    telemetry and SLO-burn-driven degradation.

Gates: ``tools/serve_gate.py`` (chaos under load — hang/kill/corrupt/
overflow with >= 4 tenants in flight, surviving requests bit-identical
to fault-free replay), wired into ``tools/chaos_gate.py``'s matrix.
CLI: ``graft_serve`` (cli/graft_serve.py).
"""

from arrow_matrix_tpu.serve.admission import (
    HBMAccountant,
    ServeCapacityError,
    request_price_bytes,
)
from arrow_matrix_tpu.serve.loadgen import (
    ba_executor_factory,
    latency_summary_ms,
    run_trace,
    slo_summary,
    smoke_serve,
    synthetic_trace,
    write_serve_artifacts,
)
from arrow_matrix_tpu.serve.request import Request, Ticket
from arrow_matrix_tpu.serve.scheduler import (
    ArrowServer,
    ExecConfig,
    degradation_ladder,
)

__all__ = [
    "ArrowServer",
    "ExecConfig",
    "HBMAccountant",
    "Request",
    "ServeCapacityError",
    "Ticket",
    "ba_executor_factory",
    "degradation_ladder",
    "latency_summary_ms",
    "request_price_bytes",
    "run_trace",
    "slo_summary",
    "smoke_serve",
    "synthetic_trace",
    "write_serve_artifacts",
]
