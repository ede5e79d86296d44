"""Reduced-scale CPU-mesh smoke run of all five parallel algorithms.

One call produces a complete, inspectable run directory:

  * ``<algo>.trace.json`` — Perfetto-loadable phase trace per
    algorithm (build / comm_account / mem_account / warmup / iterate
    (per-step spans) / gather_result);
  * ``metrics.jsonl`` — the registry event log, including
    per-iteration device time (``iteration_time_ms``),
    measured-vs-ideal collective bytes, measured-vs-predicted HBM
    bytes, and per-shard imbalance gauges;
  * ``summary.json`` — per-algorithm phase totals, step stats, the
    bytes-vs-ideal ratio, the executable memory breakdown, and the
    shard imbalance report — the machine-readable record
    ``graft_trace summarize`` / ``diff`` consume.

Construction mirrors the recompile audit (analysis/audit.py:_entries):
same generators, same seeds, same meshes — so the observability smoke
and the compile audit exercise the same shipped entry points.  Callers
must initialize a multi-device jax first (force_cpu_devices; under
pytest the conftest pool is reused).
"""

from __future__ import annotations

import json
import os
import sys
import time
from typing import Dict, Iterable, List, Optional, Tuple

import numpy as np

from arrow_matrix_tpu.obs.comm import (
    account_collectives,
    ideal_bytes_for,
    reduce_bytes_for,
)
from arrow_matrix_tpu.obs.imbalance import account_imbalance
from arrow_matrix_tpu.obs.memview import account_memory, predicted_bytes_for
from arrow_matrix_tpu.obs.metrics import MetricsRegistry
from arrow_matrix_tpu.obs.tracer import Tracer
from arrow_matrix_tpu.utils.logging import block_until_ready

ALGORITHMS = ("spmm_1d", "spmm_15d", "sell_slim", "sell_space",
              "multi_level")


def _adapters(n: int, width: int, k: int, n_dev: int,
              algorithms: Iterable[str]):
    """Yield (name, build) pairs; ``build()`` returns
    ``(obj, x, step, jit_fn, jit_args)`` where ``step(x)`` is one
    feature-carrying iteration and ``jit_fn(*jit_args)`` is the jitted
    entry point for trace-time comm accounting."""
    import jax

    from arrow_matrix_tpu.parallel.mesh import make_mesh
    from arrow_matrix_tpu.utils.graphs import (
        barabasi_albert,
        random_csr,
        random_dense,
    )

    wanted = set(algorithms)
    unknown = wanted - set(ALGORITHMS)
    if unknown:
        raise ValueError(f"unknown algorithms {sorted(unknown)}; "
                         f"choose from {ALGORITHMS}")
    devs = jax.devices()[:n_dev]

    a = random_csr(n, n, 4, seed=7).astype(np.float32)
    x_host = random_dense(n, k, seed=3)

    # Arrow decomposition shared by the slim/arrow paths (computed once
    # even when several of them run).
    arrow_state: dict = {}

    def arrow_levels():
        if not arrow_state:
            from arrow_matrix_tpu.decomposition import arrow_decomposition

            ba = barabasi_albert(n, 4, seed=11)
            arrow_state["ba"] = ba
            arrow_state["levels"] = arrow_decomposition(
                ba, width, max_levels=3, block_diagonal=True, seed=1)
        return arrow_state["ba"], arrow_state["levels"]

    if "spmm_1d" in wanted:
        def build_1d():
            from arrow_matrix_tpu.parallel.spmm_1d import MatrixSlice1D

            mesh = make_mesh((n_dev,), ("slices",), devices=devs)
            d = MatrixSlice1D(a, mesh)
            x = d.set_features(x_host)
            return (d, x, d.spmm, d._step,
                    (d.l_cols, d.l_data, d.nl_cols, d.nl_data,
                     d.send_idx, x))

        yield "spmm_1d", build_1d

    if "spmm_15d" in wanted:
        def build_15d():
            from arrow_matrix_tpu.parallel.spmm_15d import SpMM15D

            c = 2 if n_dev % 4 == 0 else 1
            mesh = make_mesh((n_dev // c, c), ("rows", "repl"),
                             devices=devs)
            d = SpMM15D(a, mesh)
            x = d.set_features(x_host)

            def step(v):
                # A blocked result (rank 4) re-enters as features via
                # as_features (square matrices only — n x n here);
                # gather_result consumes the blocked rank-4 form.
                if v.ndim == 4:
                    v = d.as_features(v)
                return d.spmm(v)

            return d, x, step, d._step, (d.a_cols, d.a_data, x)

        yield "spmm_15d", build_15d

    if "sell_slim" in wanted:
        def build_slim():
            from arrow_matrix_tpu.parallel.sell_slim import SellSlim
            from arrow_matrix_tpu.utils.graphs import random_dense as rd

            _, levels = arrow_levels()
            mesh = make_mesh((n_dev,), ("blocks",), devices=devs)
            ds = SellSlim(levels[0].matrix, width, mesh)
            x = ds.set_features(rd(levels[0].matrix.shape[0], k, seed=5))
            o = ds.ops
            return (ds, x, ds.spmm, ds._step,
                    (o.body, o.head, o.head_unsort, o.orig_pos, x))

        yield "sell_slim", build_slim

    if "sell_space" in wanted:
        def build_space():
            from arrow_matrix_tpu.parallel.sell_space import SellSpaceShared
            from arrow_matrix_tpu.utils.graphs import random_dense as rd

            _, levels = arrow_levels()
            kl = 2 if (len(levels) >= 2 and n_dev % 2 == 0) else 1
            mesh = make_mesh((kl, n_dev // kl), ("lvl", "blocks"),
                             devices=devs)
            ss = SellSpaceShared(levels[:kl], width, mesh)
            x = ss.set_features(rd(ss.n, k, seed=5))
            return (ss, x, ss.step, ss.step_fn,
                    (x,) + tuple(ss.step_operands()))

        yield "sell_space", build_space

    if "multi_level" in wanted:
        def build_multi():
            from arrow_matrix_tpu.parallel.multi_level import MultiLevelArrow

            ba, levels = arrow_levels()
            mesh = make_mesh((n_dev,), ("blocks",), devices=devs)
            ml = MultiLevelArrow(levels, width, mesh=mesh)
            x = ml.set_features(x_host[:ba.shape[0]])
            return (ml, x, ml.step, ml.step_fn,
                    (x,) + tuple(ml.step_operands()))

        yield "multi_level", build_multi


def run_smoke(run_dir: str, n: int = 256, width: int = 32, k: int = 4,
              n_dev: int = 4, iters: int = 3,
              algorithms: Iterable[str] = ALGORITHMS,
              registry: Optional[MetricsRegistry] = None) -> dict:
    """Trace + meter + comm-account each algorithm at reduced scale;
    write the run directory; return the summary dict."""
    os.makedirs(run_dir, exist_ok=True)
    reg = registry if registry is not None else MetricsRegistry(run_dir)
    summary: Dict[str, dict] = {}

    for name, build in _adapters(n, width, k, n_dev, algorithms):
        tracer = Tracer(name=name)

        with tracer.span(f"{name}/build"):
            obj, x, step, jit_fn, jit_args = build()

        with tracer.span(f"{name}/comm_account") as span_args:
            rep = account_collectives(
                name, jit_fn, *jit_args,
                ideal_bytes=ideal_bytes_for(obj, k),
                overlap_slabs=getattr(obj, "overlap_slabs", 1),
                repl=getattr(obj, "repl", 1),
                reduce_bytes=reduce_bytes_for(obj, k),
                registry=reg)
            span_args["measured_bytes"] = rep["measured_bytes"]
            span_args["source"] = rep["source"]

        with tracer.span(f"{name}/mem_account") as span_args:
            mem = account_memory(
                name, jit_fn, *jit_args,
                predicted_bytes=predicted_bytes_for(obj, k),
                registry=reg)
            span_args["measured_bytes"] = mem["measured_bytes"]
            span_args["source"] = mem["source"]
            imb = account_imbalance(name, obj, registry=reg)

        with tracer.span(f"{name}/warmup"):
            # Two calls: the second exercises the result-feedback path,
            # which can compile separately (spmm_15d's as_features
            # re-entry), so no compile lands in a measured step.
            x = block_until_ready(step(x))
            x = block_until_ready(step(x))

        steps_ms: List[float] = []
        with tracer.span(f"{name}/iterate"):
            for i in range(iters):
                t0 = time.perf_counter()
                with tracer.span(f"{name}/step", iteration=i):
                    x = block_until_ready(step(x))
                ms = (time.perf_counter() - t0) * 1e3
                steps_ms.append(ms)
                reg.record("iteration_time_ms", ms, algorithm=name)

        with tracer.span(f"{name}/gather_result"):
            y = obj.gather_result(x)
        reg.gauge("result_norm", algorithm=name).set(
            float(np.linalg.norm(y)))

        trace_file = f"{name}.trace.json"
        tracer.save(os.path.join(run_dir, trace_file))
        summary[name] = {
            "trace": trace_file,
            "phase_ms": tracer.phase_ms(),
            "steps_ms": steps_ms,
            "step_ms_mean": sum(steps_ms) / max(len(steps_ms), 1),
            "measured_bytes": rep["measured_bytes"],
            "ideal_bytes": rep["ideal_bytes"],
            "bytes_vs_ideal": rep["ratio"],
            "comm_source": rep["source"],
            "overlap_slabs": rep["overlap_slabs"],
            "exposed_comm_ms": rep["exposed_comm_ms"],
            "repl": rep["repl"],
            "reduce_bytes": rep["reduce_bytes"],
            "hbm_measured_bytes": mem["measured_bytes"],
            "hbm_predicted_bytes": mem["predicted_bytes"],
            "hbm_vs_predicted": mem["ratio"],
            "hbm_source": mem["source"],
            "memory": mem["report"],
            "imbalance": None if imb is None else {
                key: imb[key] for key in (
                    "units", "n_units", "rows_total", "nnz_total",
                    "slots_total", "nnz_max_over_mean",
                    "rows_max_over_mean", "padded_slot_waste")},
        }

    out = {
        "scale": {"n": n, "width": width, "k": k, "n_dev": n_dev,
                  "iters": iters},
        "algorithms": summary,
    }
    # graft-ledger: the smoke run's headline (mean step time of the
    # slowest algorithm) lands in a RUN-DIR-LOCAL store; the record id
    # rides the summary so tools/obs_gate.py can require it.
    try:
        from arrow_matrix_tpu.ledger import record as _ledger_record

        worst = max((alg["step_ms_mean"] for alg in summary.values()),
                    default=None)
        rec = _ledger_record(
            "smoke", "smoke_step_ms", worst,
            directory=os.path.join(run_dir, "ledger"), unit="ms",
            knobs=dict(out["scale"]),
            payload={name: {"step_ms_mean": alg["step_ms_mean"],
                            "bytes_vs_ideal": alg["bytes_vs_ideal"],
                            "hbm_vs_predicted": alg["hbm_vs_predicted"]}
                     for name, alg in summary.items()})
        out["ledger_record_id"] = rec["record_id"] if rec else None
    except Exception as e:
        print(f"[ledger] smoke record not persisted: "
              f"{type(e).__name__}: {e}", file=sys.stderr)
        out["ledger_record_id"] = None
    reg.write_jsonl(os.path.join(run_dir, "metrics.jsonl"))
    with open(os.path.join(run_dir, "summary.json"), "w",
              encoding="utf-8") as fh:
        json.dump(out, fh, indent=2, sort_keys=True)
        fh.write("\n")
    return out


def validate_run_dir(run_dir: str,
                     algorithms: Iterable[str] = ALGORITHMS) -> List[str]:
    """Structural check of a smoke run directory; returns a list of
    problems (empty = valid).  This is what tools/obs_gate.py and the
    doctor probe assert."""
    problems: List[str] = []
    spath = os.path.join(run_dir, "summary.json")
    if not os.path.isfile(spath):
        return [f"missing {spath}"]
    try:
        with open(spath, encoding="utf-8") as fh:
            summary = json.load(fh)
    except (OSError, ValueError) as e:
        return [f"unreadable summary.json: {e}"]
    algos = summary.get("algorithms", {})

    for name in algorithms:
        if name not in algos:
            problems.append(f"summary.json missing algorithm {name!r}")
            continue
        rec = algos[name]
        tpath = os.path.join(run_dir, rec.get("trace", f"{name}.trace.json"))
        if not os.path.isfile(tpath):
            problems.append(f"missing trace file {tpath}")
        else:
            try:
                with open(tpath, encoding="utf-8") as fh:
                    trace = json.load(fh)
                events = [e for e in trace.get("traceEvents", ())
                          if e.get("ph") == "X"]
                if not events:
                    problems.append(f"{tpath}: no complete ('X') events")
                for e in events:
                    if not all(f in e for f in ("name", "ph", "ts", "dur")):
                        problems.append(
                            f"{tpath}: malformed event {e!r}")
                        break
                names = {e["name"] for e in events}
                for phase in ("build", "warmup", "iterate", "step",
                              "gather_result", "comm_account",
                              "mem_account"):
                    if f"{name}/{phase}" not in names:
                        problems.append(
                            f"{tpath}: missing span {name}/{phase}")
            except (OSError, ValueError) as e:
                problems.append(f"malformed trace JSON {tpath}: {e}")
        if not rec.get("steps_ms"):
            problems.append(f"summary.json: {name} has no steps_ms")
        if rec.get("hbm_measured_bytes") is None:
            problems.append(
                f"summary.json: {name} has no memory report "
                f"(hbm_measured_bytes)")
        if rec.get("imbalance") is None:
            problems.append(
                f"summary.json: {name} has no imbalance report")

    mpath = os.path.join(run_dir, "metrics.jsonl")
    if not os.path.isfile(mpath):
        problems.append(f"missing {mpath}")
    else:
        seen: Dict[Tuple[str, str], bool] = {}
        try:
            with open(mpath, encoding="utf-8") as fh:
                for line in fh:
                    if not line.strip():
                        continue
                    ev = json.loads(line)
                    algo = ev.get("labels", {}).get("algorithm")
                    if algo:
                        seen[(ev["name"], algo)] = True
        except (ValueError, KeyError) as e:
            problems.append(f"malformed metrics.jsonl: {e}")
        else:
            for name in algorithms:
                for metric in ("iteration_time_ms", "comm_measured_bytes",
                               "hbm_measured_bytes"):
                    if not seen.get((metric, name)):
                        problems.append(
                            f"metrics.jsonl: no {metric} events for {name}")
    return problems
