"""What the program records about itself in the benchmark's process:
the spans of its process tracer and the gauges of its metrics registry
(``arrow_matrix_tpu.obs``), read by the per-layer metrics.  A program
that records none of them (an older commit) reads as None."""

from __future__ import annotations


def span_seconds(name: str) -> float | None:
    """Total seconds of the process tracer's spans called ``name``."""
    from arrow_matrix_tpu import obs

    get_tracer = getattr(obs, "get_tracer", None)
    if get_tracer is None:
        return None
    ms = get_tracer().phase_ms().get(name)
    return None if ms is None else ms / 1e3


def gauge(name: str) -> float | None:
    """The last value of the registry's unlabelled gauge ``name``."""
    from arrow_matrix_tpu import obs

    for g in obs.get_registry().snapshot()["gauges"]:
        if g["name"] == name and not g["labels"]:
            return g["value"]
    return None
