"""graft-heal: deterministic fault injection + self-healing supervision.

The dominant failure mode of the long iterated ``X := A @ X`` runs is
*runtime* faults — stalled iterations, SIGKILLed processes, non-finite
states — and until now recovery was folklore exercised only by real
outages.  This
package turns it into a tested code path:

  * :mod:`~arrow_matrix_tpu.faults.plan` — a deterministic fault plan
    (``AMT_FAULT_PLAN`` env: JSON or a path to JSON) driving thin
    injection hooks at the existing seams (executor ``step()``, mesh
    collectives, routing-table builds, artifact loads).  With no plan
    set every hook is one ``None`` check — a literal no-op adding no
    trace-time collectives and no measurable latency.
  * :mod:`~arrow_matrix_tpu.faults.supervisor` — the self-healing
    iteration-loop supervisor shared by all three SpMM CLIs:
    per-iteration watchdog, exponential backoff + bounded retry,
    checkpoint resume/rollback, and a cheap jitted finite-check on X
    with rollback-to-checkpoint on NaN/Inf.  Every fault seen and
    every recovery taken is a flight-recorder + metrics event.

Gate: ``tools/chaos_gate.py`` runs the scenario matrix (hang, kill,
corrupt artifact, NaN burst) on small BA graphs and asserts each fault
is detected, recovered, and the recovered run's final X is
bit-identical to the fault-free run.
"""

from arrow_matrix_tpu.faults.plan import (
    FaultInjected,
    FaultPlan,
    active_plan,
    clear_plan,
    inject,
    on_step,
    reload_plan,
    set_plan,
)
from arrow_matrix_tpu.faults.policy import RetryPolicy
from arrow_matrix_tpu.faults.supervisor import (
    Abort,
    NonFiniteState,
    Supervisor,
    WatchdogStalled,
    WatchdogTimeout,
    state_is_finite,
)

__all__ = [
    "Abort",
    "FaultInjected",
    "FaultPlan",
    "NonFiniteState",
    "RetryPolicy",
    "Supervisor",
    "WatchdogStalled",
    "WatchdogTimeout",
    "active_plan",
    "clear_plan",
    "inject",
    "on_step",
    "reload_plan",
    "set_plan",
    "state_is_finite",
]
