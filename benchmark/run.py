"""Chip benchmark of arrow-matrix-tpu: one cell per process.

    python -m benchmark --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is ``benchmark/workloads/<cell>.json``: a configuration
(``benchmark/configs/``), the feature width ``k``, the carriage dtype,
the iterations per job ``J`` and the driver (``benchmark/drivers/``)
that runs the program.  One run:

1. set-up: load (on a checkout's first run, generate and decompose
   first) the configuration's decomposition, build the executor, draw
   X from ``--seed``, upload it and run one warm-up job, which compiles
   the only program the window runs;
2. the window: jobs ``y = run(x, J)`` back to back on the resident X,
   each blocked until ready, for ``--seconds``; a job that starts
   inside the window runs to completion;
3. with ``--trace 1`` the window runs under the JAX profiler and the
   trace is reduced (``benchmark/trace.py``);
4. the check: the last job's whole result, permuted back, against the
   plain reference (``benchmark/check.py``), after the device state is
   freed.

The metrics are those ``BENCHMARK.json`` lists for the cell: its
end-to-end metrics, or its per-layer metrics with ``--trace 1``, each
read by ``benchmark/metrics/<name>.py``.  The last line of stdout is
one JSON object; the numbers the check compared, each beside its limit,
end it and are also the last lines of stderr.

It runs on a TPU only and exits non-zero with no result elsewhere;
``--rehearse`` allows a CPU run at a tiny size, whose result names
``platform: cpu``.  ``--carriage`` overrides the workload's carriage
(the precision control: ``bfloat16`` must come out not correct).
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import importlib.util
import json
import os
import shutil
import sys
import time
from dataclasses import dataclass, field

from benchmark import check, dataset

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")
ITEMSIZE = {"float32": 4, "bfloat16": 2}


class BenchError(Exception):
    """A run that cannot produce a result (exit 2, no result line)."""


def since_process_start() -> float:
    """Seconds since this process started (the kernel's start time on
    the boot clock), so set-up includes interpreter start and imports."""
    with open("/proc/self/stat") as f:
        stat = f.read()
    ticks = int(stat[stat.rfind(")") + 2:].split()[19])
    return (time.clock_gettime(time.CLOCK_BOOTTIME)
            - ticks / os.sysconf("SC_CLK_TCK"))


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``benchmark/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.exists(path):
        raise BenchError(f"no {kind} file {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name.replace('.', '_').replace('-', '_')}",
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(bench: dict, cell: str, trace: bool) -> list:
    """The metric entries this cell reports: end-to-end ones, or with
    ``trace`` per-layer ones.  A cell that BENCHMARK.json does not list
    (a rehearsal) reports every metric of the kind."""
    entries = bench["per_layer" if trace else "end_to_end"]
    listed = any(w["name"] == cell for w in bench["workloads"])
    return [m for m in entries
            if not listed or cell in m.get("workloads", [cell])]


@dataclass
class Spans:
    """Host spans of the harness: seconds by name, each also a
    ``bench.<name>`` annotation in the profiler's trace."""
    seconds: dict = field(default_factory=dict)

    @contextlib.contextmanager
    def __call__(self, name: str):
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench." + name):
            yield
        self.seconds[name] = (self.seconds.get(name, 0.0)
                              + time.perf_counter() - t0)


@dataclass
class Run:
    """What the metric readers read."""
    spans: dict
    setup_s: float
    compile_s: float
    window_s: float
    iterations: int
    trace: dict | None
    work: dict
    device_kind: str


def parse_args(argv):
    ap = argparse.ArgumentParser(
        prog="python -m benchmark",
        description="Run one benchmark cell and print its result line.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="allow a CPU run (tiny workloads only)")
    ap.add_argument("--carriage", choices=sorted(ITEMSIZE), default=None,
                    help="override the workload's carriage (control runs)")
    return ap.parse_args(argv)


def devices_for(chips: int, rehearse: bool) -> list:
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not rehearse:
        raise BenchError(f"JAX found no TPU (platform {platform!r}); the "
                         f"benchmark runs on the chip only (--rehearse "
                         f"allows a CPU rehearsal)")
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chip(s), JAX sees "
                         f"{len(devices)}")
    return devices[:chips]


def enable_compile_cache() -> str:
    """JAX's persistent compile cache: ``JAX_COMPILATION_CACHE_DIR`` when
    set, else a fixed directory inside the checkout."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        dataset.CACHE, "xla")
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def log(msg: str) -> None:
    print(f"[benchmark] {msg}", file=sys.stderr, flush=True)


def load_cell(workload: str) -> tuple[dict, dict, dict]:
    """BENCHMARK.json, the workload file and its configuration."""
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    wl_path = os.path.join(HERE, "workloads", workload + ".json")
    if not os.path.exists(wl_path):
        raise BenchError(f"no workload file {wl_path}")
    wl = load_json(wl_path)
    cfg = load_json(os.path.join(HERE, "configs", wl["config"] + ".json"))
    return bench, wl, cfg


def build(cfg: dict, driver, carriage: str, spans: Spans):
    """The configuration's dataset and the driver's executor for it."""
    ds = dataset.prepare(cfg, spans)
    with spans("build"):
        ex = driver.build(ds.levels, ds.width, carriage)
    ds.levels = None
    gc.collect()
    return ds, ex


def judge(ds: dataset.Dataset, wl: dict, seed: int, x, got) -> dict:
    """The check of one job's result ``got`` on the input ``x`` against
    the plain reference, through weights drawn from the seed."""
    w = check.weights(seed, wl["k"], wl["check"]["weights"])
    xw = check.project(x, w)
    indptr, indices = dataset.load_adjacency(ds)
    want = check.reference(indptr, indices, xw, wl["iterations"])
    del indptr, indices
    return check.compare(got, w, want, wl["check"])


def measure(args) -> dict:
    import jax

    bench, wl, cfg = load_cell(args.workload)
    driver = load_module("drivers", wl["driver"])
    readers = {m["name"]: (m, load_module("metrics", m["name"]))
               for m in cell_metrics(bench, args.workload, bool(args.trace))}
    carriage = args.carriage or wl["carriage"]

    devices = devices_for(wl["chips"], args.rehearse)
    dev = devices[0]
    cache_dir = enable_compile_cache()
    compile_events = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compile_events.append((event, secs))
        if event in COMPILE_EVENTS else None)
    log(f"{args.workload}: {dev.device_kind} x{len(devices)}, compile "
        f"cache {cache_dir}")

    spans = Spans()
    ds, ex = build(cfg, driver, carriage, spans)
    iters = wl["iterations"]
    with spans("features"):
        x = check.features(args.seed, ds.n, wl["k"])
    with spans("upload"):
        xd = jax.block_until_ready(driver.upload(ex, x))
    with spans("warmup"):
        jax.block_until_ready(driver.dispatch(ex, xd, iters))
    compile_s = sum(s for _, s in compile_events)
    n_compiles = len(compile_events)
    setup_s = since_process_start()
    log(f"set-up {setup_s:.3f} s: " + ", ".join(
        f"{name} {s:.3f}" for name, s in spans.seconds.items())
        + f", compile {compile_s:.3f}")

    trace_dir = os.path.join(dataset.CACHE, "trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    profile = (jax.profiler.trace(trace_dir) if args.trace
               else contextlib.nullcontext())
    y, ends = None, []
    with profile:
        with jax.profiler.TraceAnnotation("bench.window"):
            t0 = time.perf_counter()
            while not ends or ends[-1] - t0 < args.seconds:
                y = None          # the previous result is not kept
                with jax.profiler.TraceAnnotation("bench.dispatch"):
                    y = driver.dispatch(ex, xd, iters)
                with jax.profiler.TraceAnnotation("bench.block"):
                    jax.block_until_ready(y)
                ends.append(time.perf_counter())
            window_s = ends[-1] - t0
    jobs = len(ends)
    window_compiles = len(compile_events) - n_compiles
    iterations = jobs * iters
    stats = dev.memory_stats() or {}
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
    log(f"window {window_s:.3f} s, {jobs} jobs, {iterations} iterations, "
        f"{window_compiles} compilations inside it; peak {peak} B; job "
        f"seconds " + " ".join(f"{b - a:.4f}" for a, b in zip([t0] + ends,
                                                             ends))
        + f"; memory stats {json.dumps(stats)}")

    trace = None
    if args.trace:
        from benchmark import trace as trace_mod

        trace = trace_mod.reduce(trace_mod.load(
            trace_mod.find_trace(trace_dir)))
        shutil.rmtree(trace_dir, ignore_errors=True)

    with spans("fetch"):
        got = driver.fetch(ex, y)
    del y, xd, ex
    gc.collect()

    # Reading peak memory first and freeing the device state keeps the
    # reference out of both.
    with spans("reference"):
        verdict = judge(ds, wl, args.seed, x, got)
    log(f"reference {spans.seconds['reference']:.3f} s")

    run = Run(spans=dict(spans.seconds), setup_s=setup_s,
              compile_s=compile_s, window_s=window_s,
              iterations=iterations, trace=trace,
              work={"nnz": ds.nnz, "n": ds.n, "k": wl["k"],
                    "itemsize": ITEMSIZE[carriage]},
              device_kind=dev.device_kind)
    metrics = {}
    for name, (entry, reader) in readers.items():
        value = reader.read(run)
        if value is not None:
            metrics[name] = {"value": value, "unit": entry["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": verdict["ok"], "attempted": jobs,
              "failed": 0 if verdict["ok"] else 1, "metrics": metrics,
              "device": device}
    if trace is not None:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        result["breakdown"] = {"device_ops": trace["device_ops"],
                               "idle_gaps": trace["idle_gaps"]}
    result["check"] = verdict["numbers"]
    return result


def main(argv=None) -> int:
    args = parse_args(argv)
    try:
        result = measure(args)
    except BenchError as e:
        print(f"benchmark: {e}", file=sys.stderr, flush=True)
        return 2
    for name, v in result["check"].items():
        print(f"check {name} {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0
