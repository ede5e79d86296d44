"""graft-scope: runtime observability for the SpMM paths.

One layer every runtime entry point reports into, closing the loop on
the paper's headline claim (communication volume) per run:

  * :mod:`~arrow_matrix_tpu.obs.metrics` — process-level counters /
    gauges / histograms with a JSONL sink (the quantitative record);
  * :mod:`~arrow_matrix_tpu.obs.tracer` — host-side phase spans that
    double as ``jax.named_scope`` + profiler annotations and record
    their parent span, emitted as Chrome-trace / Perfetto JSON, with a
    process-wide default (``get_tracer``), plus the shared
    block-until-ready timing harness (``bench.py``'s former private
    ``_timed`` / ``_measure``);
  * :mod:`~arrow_matrix_tpu.obs.comm` — trace-time collective-byte
    accounting (utils/commstats) compared against each orchestration's
    ``ideal_comm_bytes`` paper cost model;
  * :mod:`~arrow_matrix_tpu.obs.memview` — per-executable HBM
    accounting (``compiled.memory_analysis()``) compared against each
    orchestration's ``predicted_hbm_bytes`` format-metadata model;
  * :mod:`~arrow_matrix_tpu.obs.imbalance` — per-shard nnz / padding /
    row-skew reports from the packed format metadata (the paper's
    max/mean imbalance bound as a measured gauge);
  * :mod:`~arrow_matrix_tpu.obs.flight` — graft-flight, a bounded ring
    of recent obs events eagerly flushed to disk so a wedged or killed
    run leaves a diagnosable blackbox artifact; also home of the
    request-correlation context every other obs module stamps from;
  * :mod:`~arrow_matrix_tpu.obs.pulse` — graft-pulse, the live serving
    telemetry layer: sliding-window SLO time series over the
    graft-serve event stream, a crash-readable on-disk ring, a stdlib
    Prometheus-style scrape endpoint, and the SLO-burn watchdog that
    feeds measured pressure into the degradation ladder;
  * :mod:`~arrow_matrix_tpu.obs.xray` — graft-xray, fleet-wide
    distributed tracing: router-minted trace context on every wire
    frame, per-process trace docs merged into ONE clock-offset-aligned
    Perfetto timeline (SIGKILLed workers recovered from their flight
    rings with explicit ``truncated`` markers), and the per-class
    critical-path decomposition (``graft_xray`` CLI);
  * :mod:`~arrow_matrix_tpu.obs.smoke` — a reduced-scale CPU-mesh run
    of all five parallel algorithms producing one inspectable run
    directory (traces + metrics.jsonl + summary.json);
  * :mod:`~arrow_matrix_tpu.obs.lens` /
    :mod:`~arrow_matrix_tpu.obs.costmodel` — graft-lens, the compute
    twin of the comm cost model: per-degree-ladder-level profiling of
    the folded operator, static stream-byte / padded-slot / wave
    counters derived from the kcert call metas, and a fitted
    per-level-family model ``t ≈ α·nnz + β·rows + γ·streamed_bytes``
    whose measured/predicted ratio is a first-class ledger metric
    (``graft_lens`` CLI).

CLI: ``python -m arrow_matrix_tpu.obs`` (``graft_trace``) summarizes a
run directory, diffs two runs with regression flagging, exports merged
traces, prints memory reports (``memreport``), inspects flight
artifacts (``blackbox``), and drives the smoke harness.
"""

from arrow_matrix_tpu.obs.comm import (
    account_collectives,
    auto_repl,
    hbm_budget_bytes,
    ideal_bytes_for,
    reduce_bytes_for,
)
from arrow_matrix_tpu.obs.flight import (
    FlightRecorder,
    current_request,
    request_context,
)
from arrow_matrix_tpu.obs.costmodel import (
    CostModel,
    fit_cost_model,
    predict_candidate_ms,
    predict_iter_ms,
    tier_counters,
)
from arrow_matrix_tpu.obs.imbalance import (
    account_imbalance,
    format_imbalance_report,
    shard_report_for,
)
from arrow_matrix_tpu.obs.memview import (
    account_memory,
    format_memory_report,
    format_placement,
    memory_report,
    predicted_bytes_for,
    tree_device_bytes,
)
from arrow_matrix_tpu.obs.lens import (
    attribution_fractions,
    explain_gap,
    fit_from_profile,
    profile_fold,
    ratio_points,
    record_profile,
)
from arrow_matrix_tpu.obs.metrics import (
    MetricsRegistry,
    get_registry,
    init_registry,
    set_registry,
)
from arrow_matrix_tpu.obs.pulse import (
    BurnRule,
    PulseEndpoint,
    PulseMonitor,
    SloWatchdog,
)
from arrow_matrix_tpu.obs.tracer import (
    Tracer,
    call_time_ms,
    chained_iteration_ms,
    get_tracer,
    init_tracer,
    iteration_time_ms,
    timed,
)
from arrow_matrix_tpu.obs.xray import (
    critical_path,
    merge_process_traces,
    merge_run_dir,
    new_trace_id,
    process_trace,
    recover_from_flight,
    subdivide_compute,
)

__all__ = [
    "BurnRule",
    "CostModel",
    "FlightRecorder",
    "MetricsRegistry",
    "PulseEndpoint",
    "PulseMonitor",
    "SloWatchdog",
    "Tracer",
    "account_collectives",
    "attribution_fractions",
    "current_request",
    "request_context",
    "account_imbalance",
    "account_memory",
    "auto_repl",
    "call_time_ms",
    "chained_iteration_ms",
    "critical_path",
    "explain_gap",
    "fit_cost_model",
    "fit_from_profile",
    "format_imbalance_report",
    "format_memory_report",
    "format_placement",
    "get_registry",
    "get_tracer",
    "hbm_budget_bytes",
    "ideal_bytes_for",
    "init_registry",
    "init_tracer",
    "iteration_time_ms",
    "memory_report",
    "merge_process_traces",
    "merge_run_dir",
    "new_trace_id",
    "predict_candidate_ms",
    "predict_iter_ms",
    "predicted_bytes_for",
    "process_trace",
    "profile_fold",
    "ratio_points",
    "record_profile",
    "recover_from_flight",
    "reduce_bytes_for",
    "set_registry",
    "shard_report_for",
    "subdivide_compute",
    "tier_counters",
    "timed",
    "tree_device_bytes",
]
