"""Readings of the check on many seeds in one process, for setting its
limits: the program as the cell runs it, or with ``--carriage`` the
precision control.

    python -m benchmark.readings --workload <cell> --seeds 1 2 3 \
        [--carriage bfloat16] [--rehearse]

The executor is built and compiled once; each seed then draws its X,
runs one job of the cell (its ``run(x, J)`` at the cell's sizes), and
the whole result is checked as a benchmark run checks it.  One JSON
line per seed.  Not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import json
import sys

from benchmark import check, run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python -m benchmark.readings")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--carriage", choices=sorted(run.ITEMSIZE), default=None)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    import jax

    try:
        _, wl, cfg = run.load_cell(args.workload)
        run.devices_for(wl["chips"], args.rehearse)
    except run.BenchError as e:
        print(f"benchmark.readings: {e}", file=sys.stderr, flush=True)
        return 2
    run.enable_compile_cache()
    driver = run.load_module("drivers", wl["driver"])
    carriage = args.carriage or wl["carriage"]
    spans = run.Spans()
    ds, ex = run.build(cfg, driver, carriage, spans)
    for seed in args.seeds:
        x = check.features(seed, ds.n, wl["k"])
        xd = driver.upload(ex, x)
        got = driver.fetch(ex, jax.block_until_ready(
            driver.dispatch(ex, xd, wl["iterations"])))
        del xd
        verdict = run.judge(ds, wl, seed, x, got)
        print(json.dumps({"workload": args.workload, "carriage": carriage,
                          "seed": seed, "correct": verdict["ok"],
                          "check": verdict["numbers"]}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
