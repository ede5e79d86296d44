"""Trace-time HBM memory accounting vs the format's static predictor.

``account_memory`` is the memory twin of ``account_collectives``
(obs/comm.py): it lowers+compiles a jitted entry point (compiles are
cached, so accounting a step that already ran is free), reads the
backend's per-executable memory breakdown via
``compiled.memory_analysis()`` — argument / output / temp /
generated-code bytes, all PER DEVICE — and, when the orchestration
exposes a ``predicted_hbm_bytes(k)`` model, records the
measured/predicted ratio as a first-class metric.  The ratio is the
run-level statement of the paper's memory claim: ~1.0 means the
compiled executable is resident at exactly the bytes the format
metadata (nnz, widths, padding slots) predicts; large ratios mean the
lowering materializes something the algorithm doesn't require — an
OOM-in-waiting at protocol scale.

Not every backend exposes ``memory_analysis`` (and some raise
``Unimplemented``): the fallback computes argument/output bytes from
the executable's avals instead, flagged ``source="avals"`` with temp
and generated-code bytes unknown (None) — degraded, never absent.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import numpy as np

from arrow_matrix_tpu.obs import flight


def tree_device_bytes(*trees) -> int:
    """Total bytes of every array leaf in the given pytrees, computed
    from shape metadata only (no device transfer).  Non-array leaves
    (None, scalars, ints in route tables' aux data) contribute zero."""
    import jax

    total = 0
    for leaf in jax.tree_util.tree_leaves(trees):
        size = getattr(leaf, "size", None)
        dtype = getattr(leaf, "dtype", None)
        if size is None or dtype is None:
            continue
        total += int(size) * np.dtype(dtype).itemsize
    return total


def format_placement(*trees) -> str:
    """Which device holds how much of the given pytrees: one line per
    device, summed over every array leaf's addressable shards — a
    layout that left everything on one device shows up here."""
    import jax

    per: Dict[str, list] = {}
    for leaf in jax.tree_util.tree_leaves(trees):
        for sh in getattr(leaf, "addressable_shards", ()):
            rec = per.setdefault(str(sh.device), [0, 0])
            rec[0] += int(sh.data.nbytes)
            rec[1] += 1
    lines = ["operand placement:"]
    lines += [f"  {dev}: {nb} bytes in {ns} shard(s)"
              for dev, (nb, ns) in sorted(per.items())]
    return "\n".join(lines)


def _aval_bytes(avals) -> int:
    total = 0
    for a in avals:
        shape = getattr(a, "shape", None)
        dtype = getattr(a, "dtype", None)
        if shape is None or dtype is None:
            continue
        total += int(np.prod(shape, dtype=np.int64)) * np.dtype(
            dtype).itemsize
    return total


def memory_report(jitted_fn, *args, **kwargs) -> Dict[str, Any]:
    """Per-executable memory breakdown of one jitted entry point.

    Returns ``{"source", "argument_bytes", "output_bytes",
    "temp_bytes", "generated_code_bytes", "alias_bytes",
    "total_bytes"}``.  ``source`` is ``"memory_analysis"`` when the
    backend exposed the compiled stats, ``"avals"`` for the fallback
    (argument/output from abstract values; temp/generated-code None).
    ``total_bytes`` sums every known component — the executable's
    device-resident footprint for one call.
    """
    lowered = jitted_fn.lower(*args, **kwargs)
    compiled = lowered.compile()
    try:
        ma = compiled.memory_analysis()
        report = {
            "source": "memory_analysis",
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "generated_code_bytes": int(ma.generated_code_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
        }
    except Exception:
        # Unimplemented on this backend/jaxlib: fall back to the
        # executable's abstract values — still per-device for the
        # arguments/outputs, just blind to XLA temporaries.
        in_avals = getattr(compiled, "in_avals", None) or ()
        out_avals = getattr(compiled, "out_avals", None)
        if out_avals is None:
            out_avals = ()
        report = {
            "source": "avals",
            "argument_bytes": _aval_bytes(in_avals),
            "output_bytes": _aval_bytes(out_avals),
            "temp_bytes": None,
            "generated_code_bytes": None,
            "alias_bytes": None,
        }
    # Aliased (donated) buffers are counted inside argument bytes and
    # reused for outputs — do not double-charge them in the footprint.
    known = [report["argument_bytes"], report["output_bytes"],
             report["temp_bytes"], report["generated_code_bytes"]]
    total = sum(v for v in known if v is not None)
    if report["alias_bytes"]:
        total -= report["alias_bytes"]
    report["total_bytes"] = max(int(total), 0)
    return report


def predicted_bytes_for(obj, k: int, itemsize: int = 4,
                        repl: int = 1) -> Optional[int]:
    """The orchestration's own static per-shard HBM model for one step
    at feature width ``k``, or None when it has no model.

    ``repl`` is the 2.5D planning multiplier (graft-repl): at
    replication c the per-device operator slice AND carriage grow
    exactly ×c (c-fold coarser block shards), so a c=1 executor's
    model predicts the replicated footprint as ``base × c`` — the
    number ``auto_repl`` certifies against the HBM budget before
    anything is built.  Executors without the ``repl`` kwarg (older
    models) fall back to the same ×c scaling applied outside."""
    fn = getattr(obj, "predicted_hbm_bytes", None)
    if fn is None:
        return None
    repl = max(int(repl), 1)
    try:
        return int(fn(k, itemsize=itemsize, repl=repl))
    except TypeError:
        return int(fn(k, itemsize=itemsize)) * repl


def request_bytes_for(obj, k: int, itemsize: int = 4,
                      repl: int = 1) -> Optional[int]:
    """The *incremental* per-shard HBM bytes a request of feature
    width ``k`` adds on top of the executor's resident operator —
    the per-request admission price graft-serve charges against its
    live HBM accountant.  Executors exposing ``carriage_hbm_bytes``
    (parallel/multi_level.py) answer directly; otherwise the price is
    the difference of the static model at k and at 0 (the resident
    operator alone).  None when the executor has no model at all —
    the caller must then admit pessimistically or loudly."""
    fn = getattr(obj, "carriage_hbm_bytes", None)
    if fn is not None:
        return int(fn(k, itemsize=itemsize, repl=repl))
    full = predicted_bytes_for(obj, k, itemsize=itemsize, repl=repl)
    base = predicted_bytes_for(obj, 0, itemsize=itemsize, repl=repl)
    if full is None or base is None:
        return None
    return max(int(full) - int(base), 0)


def largest_fitting_repl(base_bytes: int, budget_bytes: int,
                         choices=(1, 2, 4, 8)) -> int:
    """Largest replication factor whose predicted ×c footprint fits
    the per-device HBM budget (always at least 1 — c=1 is the
    unreplicated baseline, not a plan choice).  The memreport CLI
    prints this per executable; ``obs/comm.auto_repl`` applies the
    same certificate plus divisibility and the T(c) time model."""
    best = 1
    for c in sorted(set(int(c) for c in choices)):
        if c >= 1 and base_bytes * c <= budget_bytes:
            best = max(best, c)
    return best


def account_memory(algorithm: str, jitted_fn, *args,
                   predicted_bytes: Optional[int] = None,
                   registry=None, **kwargs) -> Dict[str, Any]:
    """Account one jitted entry point's per-device HBM bytes.

    Returns ``{"algorithm", "report" (full memory_report dict),
    "measured_bytes", "predicted_bytes", "ratio", "source"}``.
    ``measured_bytes`` is the executable's total device-resident
    footprint; ``ratio`` is None when no predictor was supplied or the
    prediction is zero.
    """
    report = memory_report(jitted_fn, *args, **kwargs)
    measured = report["total_bytes"]
    ratio = None
    if predicted_bytes:
        ratio = measured / predicted_bytes

    if registry is not None:
        registry.gauge("hbm_argument_bytes", algorithm=algorithm).set(
            report["argument_bytes"])
        registry.gauge("hbm_output_bytes", algorithm=algorithm).set(
            report["output_bytes"])
        if report["temp_bytes"] is not None:
            registry.gauge("hbm_temp_bytes", algorithm=algorithm).set(
                report["temp_bytes"])
        if report["generated_code_bytes"] is not None:
            registry.gauge("hbm_generated_code_bytes",
                           algorithm=algorithm).set(
                report["generated_code_bytes"])
        registry.gauge("hbm_measured_bytes", algorithm=algorithm).set(
            measured)
        if predicted_bytes is not None:
            registry.gauge("hbm_predicted_bytes",
                           algorithm=algorithm).set(predicted_bytes)
        if ratio is not None:
            registry.gauge("hbm_vs_predicted_ratio",
                           algorithm=algorithm).set(ratio)

    out = {
        "algorithm": algorithm,
        "report": report,
        "measured_bytes": measured,
        "predicted_bytes": predicted_bytes,
        "ratio": ratio,
        "source": report["source"],
    }
    # The flight recorder keeps the latest report whole: a run that
    # dies on device memory is diagnosed by exactly this breakdown
    # (what was being made resident, and how big).
    rec = flight.get_recorder()
    if rec is not None:
        rec.note_memory_report({
            "algorithm": algorithm, "measured_bytes": measured,
            "predicted_bytes": predicted_bytes, "ratio": ratio,
            **report})
    return out


def format_memory_report(rep: Dict[str, Any]) -> str:
    """Human-readable lines for the CLIs' ``--mem_report``."""
    r = rep["report"]

    def mb(v):
        return "n/a" if v is None else f"{v / 2**20:.2f} MiB"

    lines = [
        f"per-device executable memory ({rep['source']}):",
        f"  arguments      {mb(r['argument_bytes'])}",
        f"  outputs        {mb(r['output_bytes'])}",
        f"  temporaries    {mb(r['temp_bytes'])}",
        f"  generated code {mb(r['generated_code_bytes'])}",
        f"  total          {mb(rep['measured_bytes'])}",
    ]
    if rep["ratio"] is not None:
        lines.append(
            f"measured vs format-model prediction: "
            f"{rep['measured_bytes']} / {rep['predicted_bytes']} bytes "
            f"= {rep['ratio']:.2f}x")
    return "\n".join(lines)
