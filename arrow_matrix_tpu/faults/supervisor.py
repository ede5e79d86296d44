"""The self-healing iteration-loop supervisor.

All three SpMM CLIs run their iteration loop through one
:class:`Supervisor`: the loop body stays the CLI's own (timing spans,
validation, metrics), while the supervisor owns everything the paper's
50+-iteration production runs need when the machine misbehaves:

  * a per-iteration **watchdog** (``watchdog_s``): the body runs on a
    worker thread and a stalled iteration raises
    :class:`WatchdogTimeout` instead of wedging the run forever;
  * **bounded retry with exponential backoff**: transient failures
    (device errors, injected faults) re-run the same iteration from
    its entry state; ``max_retries`` consecutive failures end the run
    with a sealed flight recorder instead of a stack trace mid-loop;
  * a cheap **jitted finite-check** on the carried X each iteration:
    a NaN/Inf burst rolls back to the last checkpoint (or the
    iteration-entry state when none exists) rather than silently
    poisoning every subsequent iteration;
  * **checkpoint cadence + resume**: ``checkpoint_every`` saves ride
    utils/checkpoint.py (orbax or npz) and a fresh process resumes
    from the last one — the closed loop tools/chaos_gate.py proves
    bit-identical;
  * **flight-recorder + metrics events** for every fault seen and
    every recovery taken (kinds ``heal``/``fault`` in the blackbox;
    counters ``heal_faults`` / ``heal_recoveries`` in the registry).

Determinism contract: recovery re-runs the exact same compiled step
from the exact same state, so a recovered run's final X is
bit-identical to a fault-free run — asserted by tools/chaos_gate.py
for every scenario in the injection matrix.
"""

from __future__ import annotations

import contextlib
import functools
import threading
import time
from typing import Any, Callable, Optional

from arrow_matrix_tpu.faults.policy import RetryPolicy
from arrow_matrix_tpu.obs import flight


class Abort(Exception):
    """Unrecoverable, policy-level failure (validation gate, flag
    error): the supervisor never retries it."""


class WatchdogTimeout(RuntimeError):
    """An iteration exceeded the watchdog budget but the stalled
    attempt eventually drained — the iteration is retriable."""


class WatchdogStalled(RuntimeError):
    """An iteration exceeded the watchdog budget and never drained
    within the grace window: a genuine wedge.  In-process retry is
    impossible (the stalled thread cannot be killed); the supervisor
    seals the blackbox and re-raises so process-level recovery
    (checkpoint resume in a fresh process) takes over."""


class NonFiniteState(RuntimeError):
    """The carried X failed the finite-check after an iteration."""


@functools.lru_cache(maxsize=1)
def _finite_all():
    """One cached jitted reduction (the mesh.py ``_replicator`` idiom:
    a fresh jit per call would recompile every iteration)."""
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda a: jnp.all(jnp.isfinite(a)))


def state_is_finite(x) -> bool:
    """True when every element of ``x`` is finite.  One jitted
    all-reduce; the host reads back a single scalar — the iteration
    loop it guards already blocks on the step result, so this adds one
    tiny kernel, not a new sync point."""
    return bool(_finite_all()(x))


class Supervisor:
    """Run ``body(x, it) -> y`` for ``it`` in ``[start, stop)`` with
    watchdog / retry / rollback / checkpointing around it.

    ``carry=True`` threads ``y`` into the next iteration's ``x`` (the
    iterated ``X := A @ X`` run); ``carry=False`` keeps ``x`` fixed
    (the fresh-input benchmark loops).  ``layout`` tags checkpoints so
    a resume under a different execution mode fails loudly instead of
    silently permuting rows (utils/checkpoint.py).
    """

    def __init__(self, name: str, *, carry: bool = True,
                 watchdog_s: float = 0.0,
                 watchdog_grace_s: float = 30.0,
                 max_retries: int = 2,
                 backoff_s: float = 0.05,
                 backoff_factor: float = 2.0,
                 policy: Optional[RetryPolicy] = None,
                 checkpoint_path: Optional[str] = None,
                 checkpoint_every: int = 0,
                 finite_check: bool = True,
                 layout: Optional[str] = None,
                 registry=None,
                 tracer=None,
                 verbose: bool = True,
                 canonicalize: Optional[Callable[[Any], Any]] = None):
        self.name = name
        self.carry = carry
        # The retry/backoff/watchdog knobs live in one shared
        # RetryPolicy (faults/policy.py) so the batch CLIs and
        # graft-serve run the identical recovery behavior.  The loose
        # keyword form is kept for existing callers; an explicit
        # ``policy`` wins.
        if policy is None:
            policy = RetryPolicy(
                max_retries=int(max_retries),
                backoff_s=float(backoff_s),
                backoff_factor=float(backoff_factor),
                watchdog_s=float(watchdog_s or 0.0),
                watchdog_grace_s=float(watchdog_grace_s))
        self.policy = policy
        self.watchdog_s = float(policy.watchdog_s or 0.0)
        self.watchdog_grace_s = float(policy.watchdog_grace_s)
        self.max_retries = int(policy.max_retries)
        self.backoff_s = float(policy.backoff_s)
        self.backoff_factor = float(policy.backoff_factor)
        self.checkpoint_path = checkpoint_path
        self.checkpoint_every = int(checkpoint_every)
        self.finite_check = finite_check
        self.layout = layout
        self.registry = registry
        self.tracer = tracer
        self.verbose = verbose
        #: Optional device-level map applied to the carried state right
        #: before every save (graft-repl: the 2.5D executors carry
        #: per-replica-group PARTIAL slabs — ``fetch_replicated`` in the
        #: checkpoint layer would silently persist replica 0's partial
        #: view.  The executors' ``merge_carries`` produces the fully
        #: replicated canonical state, which is a bit-exact resume
        #: point because the step re-extracts each group's own slab).
        self.canonicalize = canonicalize
        self.faults_seen = 0
        self.recoveries = 0
        self.last_checkpoint_step: Optional[int] = None

    # -- events ------------------------------------------------------------

    def _span(self, name: str, **attrs):
        """A tracer span when graft-serve attached a tracer, else a
        no-op — attempt/resume/checkpoint phases then appear on the
        same request-correlated Perfetto track as the scheduler's
        admission and batch spans (the request context is ambient)."""
        if self.tracer is None:
            return contextlib.nullcontext()
        return self.tracer.span(name, supervisor=self.name, **attrs)

    def _event(self, kind: str, name: str, **data) -> None:
        flight.record(kind, name, supervisor=self.name, **data)
        if self.registry is not None:
            self.registry.counter(f"heal_{name}",
                                  supervisor=self.name).inc()
        if self.verbose:
            extra = " ".join(f"{k}={v}" for k, v in data.items())
            print(f"[graft-heal {self.name}] {name} {extra}")

    def _fault(self, reason: str, it: int, err: Exception) -> None:
        self.faults_seen += 1
        self._event("fault", reason, iteration=it,
                    error=f"{type(err).__name__}: {err}")

    def _recovery(self, action: str, it: int, **data) -> None:
        self.recoveries += 1
        self._event("heal", action, iteration=it, **data)

    # -- checkpointing -----------------------------------------------------

    def resume(self, like) -> Optional[tuple]:
        """Load the last checkpoint (None when absent/not configured);
        returns ``(x, step)`` restored onto ``like``'s sharding.

        Every successful load emits a ``resumed`` flight event carrying
        this supervisor's name (the request/run id) — the checkpoint
        layer's own event has the path but not the identity of the run
        that adopted the state.  A checkpoint predating the version/
        layout tags (pre-canonicalize, "legacy") cannot be verified
        against the current layout: it still loads, but with a LOUD
        warning and ``legacy=True`` on the event, never a crash.
        """
        if not self.checkpoint_path:
            return None
        from arrow_matrix_tpu.utils.checkpoint import (
            checkpoint_meta,
            load_state,
        )

        with self._span("resume", path=self.checkpoint_path):
            meta = checkpoint_meta(self.checkpoint_path)
            state = load_state(self.checkpoint_path, like=like,
                               layout=self.layout)
        if state is not None:
            self.last_checkpoint_step = state[1]
            legacy = meta is None or int(meta.get("version") or 0) < 1
            if legacy:
                import sys

                print(f"[graft-heal {self.name}] WARNING: checkpoint "
                      f"at {self.checkpoint_path} predates the "
                      f"version/layout tags (legacy format) — the "
                      f"carried-X layout cannot be verified against "
                      f"{self.layout!r}; resuming anyway",
                      file=sys.stderr)
            self._event("heal", "resumed", step=state[1],
                        path=self.checkpoint_path, legacy=legacy)
        return state

    def _save(self, x, step: int) -> None:
        from arrow_matrix_tpu.utils.checkpoint import save_state

        with self._span("checkpoint", step=step):
            if self.canonicalize is not None:
                x = self.canonicalize(x)
            save_state(self.checkpoint_path, x, step,
                       layout=self.layout)
        self.last_checkpoint_step = step
        self._event("heal", "checkpointed", step=step)

    def _rollback(self, x_entry, it: int, like):
        """State to retry from after a fault at iteration ``it``: the
        last checkpoint when one exists (the NaN-burst contract —
        anything the burst may have touched is discarded), else the
        iteration-entry state."""
        if self.carry and self.checkpoint_path:
            state = self.resume(like)
            if state is not None:
                x_ck, step_ck = state
                if step_ck <= it:
                    self._recovery("rollback_to_checkpoint", it,
                                   resumed_step=step_ck)
                    return x_ck, step_ck
        self._recovery("retry_from_iteration_entry", it)
        return x_entry, it

    # -- the supervised attempt -------------------------------------------

    def _attempt(self, body: Callable, x, it: int):
        if self.watchdog_s <= 0:
            return body(x, it)
        box: dict = {}
        done = threading.Event()

        def run():
            try:
                box["y"] = body(x, it)
            except BaseException as e:  # delivered to the caller below
                box["e"] = e
            finally:
                done.set()

        t = threading.Thread(target=run, daemon=True,
                             name=f"heal-{self.name}-it{it}")
        t.start()
        if not done.wait(self.watchdog_s):
            self._fault("watchdog_timeout", it,
                        WatchdogTimeout(f"iteration {it} exceeded "
                                        f"{self.watchdog_s:.3f}s"))
            # A python thread cannot be killed; give the stall a
            # bounded grace to drain (an injected hang does, a wedged
            # PJRT transfer does not) and retry only when it did.
            if not done.wait(self.watchdog_grace_s):
                raise WatchdogStalled(
                    f"iteration {it} still running after watchdog "
                    f"({self.watchdog_s:.3f}s) + grace "
                    f"({self.watchdog_grace_s:.1f}s); process-level "
                    f"recovery (checkpoint resume) required")
            raise WatchdogTimeout(
                f"iteration {it} exceeded the {self.watchdog_s:.3f}s "
                f"watchdog (drained during grace; retrying)")
        if "e" in box:
            raise box["e"]
        return box["y"]

    # -- the loop ----------------------------------------------------------

    def run(self, body: Callable[[Any, int], Any], x0, start_it: int,
            stop_it: int) -> tuple:
        """Supervised loop; returns ``(x_final, ok)``.

        ``body`` raising :class:`Abort` ends the run immediately with
        ``ok=False`` (policy failures are not retried);
        :class:`WatchdogStalled` is re-raised after sealing the
        blackbox; anything else is a fault: backoff, rollback, retry.
        """
        x = x0
        it = start_it
        consecutive = 0
        while it < stop_it:
            try:
                # The attempt span carries iteration + retry ordinal
                # (and, under graft-serve, the ambient request id), so
                # a retried iteration shows up as two attempt spans —
                # the first with an ``error`` arg — on one track.
                with self._span("attempt", iteration=it,
                                retry=consecutive):
                    y = self._attempt(body, x, it)
                    if (self.carry and self.finite_check
                            and not state_is_finite(y)):
                        raise NonFiniteState(
                            f"carried X contains NaN/Inf after "
                            f"iteration {it}")
            except Abort as e:
                self._event("fault", "aborted", iteration=it,
                            error=str(e))
                return x, False
            except WatchdogStalled as e:
                rec = flight.get_recorder()
                if rec is not None:
                    rec.seal(f"watchdog stalled: {e}")
                raise
            except Exception as e:
                reason = ("nan_detected"
                          if isinstance(e, NonFiniteState) else
                          "watchdog_timeout"
                          if isinstance(e, WatchdogTimeout) else
                          "iteration_error")
                if not isinstance(e, WatchdogTimeout):
                    # watchdog faults were already recorded at expiry
                    # (before the grace join, so a subsequent SIGKILL
                    # still leaves the fault in the blackbox).
                    self._fault(reason, it, e)
                consecutive += 1
                if consecutive > self.max_retries:
                    self._event("fault", "retries_exhausted",
                                iteration=it,
                                retries=self.max_retries)
                    return x, False
                time.sleep(self.policy.delay_s(consecutive,
                                               salt=f"{self.name}:it{it}"))
                x, it = self._rollback(x, it, like=x0)
                continue
            consecutive = 0
            if self.carry:
                x = y
            it += 1
            if (self.carry and self.checkpoint_path
                    and self.checkpoint_every > 0
                    and it % self.checkpoint_every == 0
                    and it < stop_it):
                self._save(x, it)
        if self.carry and self.checkpoint_path and stop_it > start_it:
            # Final-state save: the artifact chaos_gate compares
            # bit-for-bit, and the resume point for a longer rerun.
            self._save(x, stop_it)
        return x, True

    def summary(self) -> dict:
        return {"supervisor": self.name, "faults_seen": self.faults_seen,
                "recoveries": self.recoveries,
                "last_checkpoint_step": self.last_checkpoint_step}
