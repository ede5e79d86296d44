"""Trace-time recompile audit (graft-lint engine 2).

The AST rules catch hazards syntactically; this engine catches them
*behaviorally*: it builds each core SpMM entry point on the host-CPU
virtual mesh, runs the jitted step twice with same-shape inputs, and
asserts the second call hits the compilation cache — zero recompiles.
A recompile on call two means a drifting static argument, an
unhashable cache key, or a fresh-jit-per-call factory: exactly the
regressions that turn the iterated ``X := A @ X`` bench from
compute-bound into compile-bound.

Alongside the cache check, each entry point is abstract-evaluated
(``jax.eval_shape``) and the output aval recorded, so shape/dtype
drift in the step contract also diffs in review.  Results land in a
manifest (default ``bench_cache/compile_manifest.json``) that is
checked in; ``tests/test_analysis.py`` re-runs the audit at reduced
scale inside tier-1.

Run standalone: ``python -m arrow_matrix_tpu.analysis audit``.
"""

from __future__ import annotations

import json
import logging
import os
from typing import Callable, Optional

import numpy as np


def _cache_size(fn) -> Optional[int]:
    """Entries in a jitted callable's compilation cache (None when the
    installed jax lacks the introspection hook)."""
    try:
        return int(fn._cache_size())
    except Exception:
        return None


class _CompileLogCounter(logging.Handler):
    """Fallback compile counter for jax without ``_cache_size``:
    counts log_compiles records while attached."""

    def __init__(self):
        super().__init__(level=logging.DEBUG)
        self.count = 0

    def emit(self, record):
        msg = record.getMessage()
        if "ompil" in msg:   # "Compiling ..." / "Finished XLA compilation"
            self.count += 1

    def __enter__(self):
        import jax

        self._prev = jax.config.jax_log_compiles
        jax.config.update("jax_log_compiles", True)
        logging.getLogger("jax").addHandler(self)
        return self

    def __exit__(self, *exc):
        import jax

        logging.getLogger("jax").removeHandler(self)
        jax.config.update("jax_log_compiles", False if not self._prev
                          else self._prev)


def _measure(step_fn, call: Callable[[], object]) -> dict:
    """Run ``call`` twice; return compile counts per call (preferring
    the jit cache size, falling back to compile-log counting)."""
    before = _cache_size(step_fn)
    if before is not None:
        call()
        after_first = _cache_size(step_fn)
        call()
        after_second = _cache_size(step_fn)
        return {"method": "cache_size",
                "compiles_first_call": after_first - before,
                "recompiles_second_call": after_second - after_first}
    with _CompileLogCounter() as c1:
        call()
    with _CompileLogCounter() as c2:
        call()
    return {"method": "log_compiles",
            "compiles_first_call": c1.count,
            "recompiles_second_call": c2.count}


def _aval(tree) -> object:
    import jax

    return jax.tree_util.tree_map(
        lambda s: {"shape": list(s.shape), "dtype": str(s.dtype)}, tree)


def audit_entry(name: str, step_fn, call: Callable[[], object],
                eval_shape: Callable[[], object]) -> dict:
    rec = {"entry": name}
    rec.update(_measure(step_fn, call))
    try:
        rec["abstract_eval"] = _aval(eval_shape())
    except Exception as e:  # aval is informational; the count is the gate
        rec["abstract_eval"] = f"error: {type(e).__name__}: {e}"
    rec["ok"] = (rec["recompiles_second_call"] == 0
                 and rec["compiles_first_call"] >= 1)
    return rec


# ---------------------------------------------------------------------------
# The audited entry points
# ---------------------------------------------------------------------------


def _entries(n: int, width: int, k: int, n_dev: int):
    """Build each core SpMM entry point at audit scale and yield
    (name, step_fn, call, eval_shape) quadruples."""
    import jax

    from arrow_matrix_tpu.decomposition import arrow_decomposition
    from arrow_matrix_tpu.parallel.mesh import make_mesh
    from arrow_matrix_tpu.utils.graphs import (
        barabasi_albert,
        random_csr,
        random_dense,
    )

    devs = jax.devices()[:n_dev]
    a = random_csr(n, n, 4, seed=7).astype(np.float32)
    x_host = random_dense(n, k, seed=3)

    # parallel/spmm_1d.py — PETSc-style 1-D row partition.
    from arrow_matrix_tpu.parallel.spmm_1d import MatrixSlice1D

    mesh1 = make_mesh((n_dev,), ("slices",), devices=devs)
    d1 = MatrixSlice1D(a, mesh1)
    x1 = d1.set_features(x_host)
    yield ("spmm_1d.MatrixSlice1D", d1._step,
           lambda: jax.block_until_ready(d1.spmm(x1)),
           lambda: jax.eval_shape(d1.spmm, x1))

    # parallel/spmm_15d.py — A-stationary 1.5D partition.
    from arrow_matrix_tpu.parallel.spmm_15d import SpMM15D

    c = 2 if n_dev % 4 == 0 else 1
    mesh15 = make_mesh((n_dev // c, c), ("rows", "repl"), devices=devs)
    d15 = SpMM15D(a, mesh15)
    x15 = d15.set_features(x_host)
    yield ("spmm_15d.SpMM15D", d15._step,
           lambda: jax.block_until_ready(d15.spmm(x15)),
           lambda: jax.eval_shape(d15.spmm, x15))

    # Arrow decomposition shared by the slim paths.
    ba = barabasi_albert(n, 4, seed=11)
    levels = arrow_decomposition(ba, width, max_levels=3,
                                 block_diagonal=True, seed=1)
    meshb = make_mesh((n_dev,), ("blocks",), devices=devs)

    # parallel/sell_slim.py — padding-free distributed slim layout.
    from arrow_matrix_tpu.parallel.sell_slim import SellSlim

    ds = SellSlim(levels[0].matrix, width, meshb)
    xs = ds.set_features(random_dense(levels[0].matrix.shape[0], k, seed=5))
    yield ("sell_slim.SellSlim", ds._step,
           lambda: jax.block_until_ready(ds.spmm(xs)),
           lambda: jax.eval_shape(ds.spmm, xs))

    # parallel/multi_level.py — the full multi-level arrow operator.
    from arrow_matrix_tpu.parallel.multi_level import MultiLevelArrow

    ml = MultiLevelArrow(levels, width, mesh=meshb)
    xm = ml.set_features(x_host[:ba.shape[0]])
    yield ("multi_level.MultiLevelArrow", ml._step,
           lambda: jax.block_until_ready(ml.step(xm)),
           lambda: jax.eval_shape(ml.step, xm))


def run_audit(out_path: str = os.path.join("bench_cache",
                                           "compile_manifest.json"),
              n: int = 512, width: int = 64, k: int = 8,
              n_dev: int = 4, write: bool = True) -> dict:
    """Audit every core SpMM entry point; return (and write) the
    manifest.  Requires an initialized multi-device jax (the CLI path
    forces a virtual CPU pool first; under pytest the conftest pool is
    reused)."""
    import datetime

    import jax

    entries = [audit_entry(*quad) for quad in _entries(n, width, k, n_dev)]
    manifest = {
        "generated_by": "python -m arrow_matrix_tpu.analysis audit",
        "timestamp": datetime.datetime.now(
            datetime.timezone.utc).isoformat(timespec="seconds"),
        "jax_version": jax.__version__,
        "platform": jax.devices()[0].platform,
        "n_devices": n_dev,
        "scale": {"n": n, "width": width, "k": k},
        "entries": entries,
        "ok": all(e["ok"] for e in entries),
    }
    if write:
        os.makedirs(os.path.dirname(out_path) or ".", exist_ok=True)
        with open(out_path, "w", encoding="utf-8") as fh:
            json.dump(manifest, fh, indent=2, sort_keys=True)
            fh.write("\n")
    return manifest


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        prog="graft_lint audit", description=__doc__.splitlines()[0])
    ap.add_argument("--out", default=os.path.join("bench_cache",
                                                  "compile_manifest.json"))
    ap.add_argument("--devices", type=int, default=4,
                    help="virtual CPU devices (forced before jax init)")
    ap.add_argument("--n", type=int, default=512)
    ap.add_argument("--width", type=int, default=64)
    ap.add_argument("--k", type=int, default=8)
    args = ap.parse_args(argv)

    # The audit is a CPU-trace exercise by contract: force the virtual
    # pool BEFORE the first backend touch (conftest does the same for
    # tests; a TPU would measure the wrong thing).
    from arrow_matrix_tpu.utils.platform import force_cpu_devices

    force_cpu_devices(args.devices)

    manifest = run_audit(out_path=args.out, n=args.n, width=args.width,
                         k=args.k, n_dev=args.devices)
    for e in manifest["entries"]:
        mark = "ok  " if e["ok"] else "FAIL"
        print(f"[{mark}] {e['entry']}: {e['compiles_first_call']} compile(s) "
              f"on call 1, {e['recompiles_second_call']} recompile(s) on "
              f"call 2 [{e['method']}]")
    print(f"manifest: {args.out}")
    print("audit passed" if manifest["ok"] else "AUDIT FAILED")
    return 0 if manifest["ok"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
