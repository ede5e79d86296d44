"""Phase tracer + the shared device-timing harness.

Host-side spans (``Tracer.span``) measure wall time per phase and emit
Chrome-trace / Perfetto JSON; each span also enters ``jax.named_scope``
and ``jax.profiler.TraceAnnotation`` so that when any jit tracing or a
profiler capture happens inside the span, the device-side record
carries the same phase names as the host-side one.  A span records the
span that was open around it (its parent), whichever tracer holds
that one.  :func:`get_tracer` is the process-wide tracer that library
code (the executor build) records into, beside
:func:`~arrow_matrix_tpu.obs.metrics.get_registry`.

The timing helpers are the one honest way to time async-dispatch jax
work (graft-lint R7 flags the dishonest way):

  * :func:`timed` — seconds for one call, result blocked until ready;
  * :func:`iteration_time_ms` — per-iteration device ms via
    block-until-ready around each step;
  * :func:`chained_iteration_ms` — ms/iter via a chained on-device run
    ending in a scalar host fetch with the dispatch round-trip
    subtracted (``bench.py``'s former private ``_measure``: one
    dispatch for the whole chain, so per-step host dispatch stays out
    of the number).
"""

from __future__ import annotations

import contextlib
import contextvars
import itertools
import json
import os
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import numpy as np

from arrow_matrix_tpu.obs import flight
from arrow_matrix_tpu.utils.logging import block_until_ready


@dataclass
class Span:
    """One completed phase: Chrome-trace complete event ("ph": "X").

    ``span_id`` is unique in the process; ``parent`` / ``parent_id``
    name the span that was open around this one (None at the top)."""

    name: str
    ts_us: float
    dur_us: float
    tid: int = 0
    args: Dict[str, Any] = field(default_factory=dict)
    span_id: int = 0
    parent: Optional[str] = None
    parent_id: Optional[int] = None


_SPAN_IDS = itertools.count(1)
# (name, span_id) of the spans open in this context, innermost last.
_OPEN: contextvars.ContextVar = contextvars.ContextVar(
    "obs_open_spans", default=())


@contextlib.contextmanager
def _device_annotation(name: str):
    """Enter jax.named_scope + profiler TraceAnnotation when jax is
    importable; silently a no-op otherwise so the tracer works in
    jax-free tooling processes."""
    with contextlib.ExitStack() as stack:
        try:
            import jax

            stack.enter_context(jax.named_scope(name))
            stack.enter_context(jax.profiler.TraceAnnotation(name))
        except ImportError:
            pass
        yield


class Tracer:
    """Collects spans for one run; serializes to Chrome trace JSON.

    Spans record even when the body raises (try/finally), so a failed
    phase still shows up — with an ``error`` arg — in the trace.
    """

    def __init__(self, name: str = "run"):
        self.name = name
        self.spans: List[Span] = []
        self._epoch = time.perf_counter()
        # Wall-clock anchor for the monotonic span epoch: a span's
        # absolute time is ``epoch_unix + ts_us/1e6``.  graft-xray uses
        # this to merge per-process traces onto one fleet timeline.
        self.epoch_unix = time.time()

    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        """Time a phase; nested spans render nested in Perfetto, and
        each records the innermost span open around it (in any tracer
        of this context) as its parent.

        Inside a :func:`~arrow_matrix_tpu.obs.flight.request_context`
        scope the span args carry ``request_id`` (and ``tenant``), so
        one Perfetto track reconstructs a served request end-to-end —
        admission, batch formation, supervised attempts, kernel phases
        — across the threads that handled it (explicit attrs win)."""
        args = dict(attrs)
        ctx = flight.current_request()
        if ctx is not None:
            for k, v in ctx.items():
                args.setdefault(k, v)
        open_spans = _OPEN.get()
        parent, parent_id = open_spans[-1] if open_spans else (None, None)
        span_id = next(_SPAN_IDS)
        token = _OPEN.set(open_spans + ((name, span_id),))
        tic = time.perf_counter()
        try:
            with _device_annotation(name):
                yield args
        except BaseException as exc:
            args.setdefault("error", f"{type(exc).__name__}: {exc}")
            raise
        finally:
            toc = time.perf_counter()
            _OPEN.reset(token)
            self.spans.append(Span(
                name=name,
                ts_us=(tic - self._epoch) * 1e6,
                dur_us=(toc - tic) * 1e6,
                args=args,
                span_id=span_id,
                parent=parent,
                parent_id=parent_id,
            ))
            # Mirror into the flight recorder ring (no-op unless
            # installed): the last completed spans name the phase a
            # wedge killed.
            flight.record("span", name, ms=(toc - tic) * 1e3,
                          **({"error": args["error"]}
                             if "error" in args else {}))

    def phase_ms(self) -> Dict[str, float]:
        """Total host ms per span name."""
        out: Dict[str, float] = {}
        for s in self.spans:
            out[s.name] = out.get(s.name, 0.0) + s.dur_us / 1e3
        return out

    def to_chrome_trace(self) -> dict:
        events = []
        for s in self.spans:
            args = dict(s.args, span_id=s.span_id)
            if s.parent is not None:
                args.update(parent=s.parent, parent_id=s.parent_id)
            events.append({
                "name": s.name,
                "ph": "X",
                "ts": s.ts_us,
                "dur": s.dur_us,
                "pid": 1,
                "tid": s.tid,
                "args": args,
            })
        # Chronological order helps Perfetto's importer nest events.
        events.sort(key=lambda e: e["ts"])
        events.insert(0, {
            "name": "process_name",
            "ph": "M",
            "pid": 1,
            "tid": 0,
            "args": {"name": self.name},
        })
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def save(self, path: str) -> str:
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        with open(path, "w", encoding="utf-8") as fh:
            json.dump(self.to_chrome_trace(), fh, indent=1)
        return path


_DEFAULT = Tracer("process")


def get_tracer() -> Tracer:
    """The process-wide tracer library code records into."""
    return _DEFAULT


def init_tracer(name: str = "run") -> Tracer:
    """Reset the process-wide tracer for a new run."""
    global _DEFAULT
    _DEFAULT = Tracer(name)
    return _DEFAULT


def timed(fn) -> float:
    """Seconds for one call of ``fn``, blocking on its result so async
    dispatch cannot fake an instant return (bench.py's former
    ``_timed``, made honest by default)."""
    t0 = time.perf_counter()
    block_until_ready(fn())
    return time.perf_counter() - t0


def call_time_ms(fn, *args, iters: int = 5, warmup: int = 1,
                 registry=None, name: str = "call", **labels) -> float:
    """Mean ms per call of ``fn(*args)`` with fixed arguments —
    ``tools/profile_tpu.py``'s former private ``timeit``, promoted to
    the shared harness so every profiler times one way.

    Unlike :func:`iteration_time_ms` the output is NOT fed back (the
    per-level launches a profile times take operands of differing
    shapes); every call is individually blocked until ready, so a
    slow first wave cannot hide behind async dispatch.  Records each
    sample into ``registry`` as ``call_time_ms`` when one is given.
    """
    for _ in range(max(warmup, 0)):
        block_until_ready(fn(*args))
    samples: List[float] = []
    for _ in range(max(iters, 1)):
        ms = timed(lambda: fn(*args)) * 1e3
        samples.append(ms)
        if registry is not None:
            registry.record("call_time_ms", ms, call=name, **labels)
    return sum(samples) / len(samples)


def iteration_time_ms(step_fn, x, iters: int, warmup: int = 1,
                      registry=None, name: str = "step",
                      **labels) -> List[float]:
    """Per-iteration device time: block_until_ready around each step.

    Feeds each output back as the next input (the bench's
    ``X := A @ X`` pattern).  Records every sample into ``registry``
    as ``iteration_time_ms`` when one is given.
    """
    for _ in range(max(warmup, 0)):
        x = block_until_ready(step_fn(x))
    out: List[float] = []
    for _ in range(iters):
        t0 = time.perf_counter()
        x = block_until_ready(step_fn(x))
        ms = (time.perf_counter() - t0) * 1e3
        out.append(ms)
        if registry is not None:
            registry.record("iteration_time_ms", ms, step=name, **labels)
    return out


def chained_sampler(run_fn, x, iters: int):
    """Compile-and-warm a chained measurement, return a zero-arg
    callable producing one ms/iter sample per call.

    Splitting compile/warmup from sampling lets a caller timing MANY
    programs (graft-lens's per-level prefixes) interleave sampling
    sweeps across all of them and take per-program minima: slow host
    load drift then lands on whole sweeps instead of whole programs,
    and the minimum discards it."""
    def chain(n: int) -> float:
        t0 = time.perf_counter()
        xd = run_fn(x, n) if n else x
        float(np.asarray(xd[0, 0]))
        return time.perf_counter() - t0

    chain(iters)  # compile + warmup at the benchmark length
    rtt = min(chain(0) for _ in range(3))

    def sample() -> float:
        return max((chain(iters) - rtt) / iters, 1e-9) * 1e3

    return sample


def chained_iteration_ms(run_fn, x, iters: int) -> float:
    """ms/iter via chained on-device iteration (`lax.scan`) ending in a
    scalar host fetch, with the dispatch+fetch round-trip subtracted."""
    return chained_sampler(run_fn, x, iters)()
