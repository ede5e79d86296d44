"""``graft_serve`` — run the always-on multi-tenant SpMM server over a
deterministic synthetic load.

Builds a Barabasi-Albert arrow decomposition (the resident operator),
starts :class:`~arrow_matrix_tpu.serve.ArrowServer` with admission
control against the HBM budget, and drives it with the deterministic
load generator (serve/loadgen.py): no wall-clock randomness, so two
runs of the same flags produce bit-identical per-request results —
the property tools/serve_gate.py's kill scenario compares across a
SIGKILL + checkpoint resume.

Prints the SLO report (requests/s, p50/p99 latency, shed/rejected
census, HBM occupancy) and writes ``serve_summary.json`` +
``metrics.jsonl`` + the flight recorder under ``--obs_dir``.  With
``--pulse``, attaches the graft-pulse telemetry layer (obs/pulse.py):
a request-correlated Perfetto trace (``serve_trace.json``), the
windowed SLO time series ring (``pulse_ring.json`` +
``pulse_metrics.prom``), an optional live scrape endpoint
(``--pulse_port``), and the SLO-burn watchdog feeding the degradation
ladder.  Exits non-zero only when a request FAILED (shed/rejected are
explicit, policy-level outcomes, not server failures).
"""

from __future__ import annotations

import argparse
import json
import sys
import time


def build_parser() -> argparse.ArgumentParser:
    from arrow_matrix_tpu.cli.common import (
        add_device_args,
        add_heal_args,
    )

    p = argparse.ArgumentParser(
        prog="graft_serve", description=__doc__.splitlines()[0])
    p.add_argument("--vertices", type=int, default=256)
    p.add_argument("--width", type=int, default=32,
                   help="arrow width of the resident decomposition")
    p.add_argument("--features", type=int, default=4,
                   help="feature width k of every synthetic request")
    p.add_argument("--tenants", type=int, default=4)
    p.add_argument("--requests", type=int, default=16)
    p.add_argument("--iterations", type=int, default=3,
                   help="SpMM iterations per request")
    p.add_argument("--seed", type=int, default=11)
    p.add_argument("--fmt", type=str, default="fold",
                   choices=["fold", "ell"],
                   help="resident executor format: 'fold' is the "
                        "single-chip SELL fold (ladder pallas_sell -> "
                        "xla), 'ell' shards level blocks over a "
                        "--devices mesh (ladder overlap S -> 1)")
    p.add_argument("--kernel", type=str, default="xla",
                   choices=["xla", "pallas_sell"],
                   help="base rung kernel (fold only); faults degrade "
                        "pallas_sell -> xla")
    p.add_argument("--overlap_slabs", type=int, default=1,
                   help="base rung overlap sub-slabs (--fmt ell on a "
                        "mesh; the one-chip fold has no exchange to "
                        "overlap and refuses S > 1)")
    p.add_argument("--queue", type=int, default=16,
                   help="bounded queue capacity; overflow sheds "
                        "explicitly")
    p.add_argument("--max_batch_k", type=int, default=0,
                   help="dynamic batching: concatenate compatible "
                        "queued requests along the feature axis up to "
                        "this combined width (0 disables)")
    p.add_argument("--hbm_budget_mb", type=float, default=0.0,
                   help="HBM budget for admission control in MiB "
                        "(0 = the platform/AMT_HBM_GB budget)")
    p.add_argument("--degrade_after", type=int, default=2,
                   help="recovered faults per tenant before its rung "
                        "degrades")
    p.add_argument("--deadline", type=float, default=0.0,
                   help="per-request queueing deadline seconds "
                        "(0 = none); expired requests are shed "
                        "explicitly at dequeue")
    p.add_argument("--obs_dir", type=str, default=None,
                   help="run directory for serve_summary.json, "
                        "metrics.jsonl, and the flight recorder")
    p.add_argument("--pulse", action="store_true",
                   help="attach the graft-pulse live telemetry layer: "
                        "windowed SLO time series + burn watchdog, "
                        "request-correlated Perfetto trace "
                        "(serve_trace.json) and pulse_ring.json/"
                        "pulse_metrics.prom under --obs_dir")
    p.add_argument("--pulse_window", type=float, default=0.5,
                   help="pulse sliding-window width in seconds")
    p.add_argument("--pulse_port", type=int, default=-1,
                   help="serve /metrics + /pulse.json on this port "
                        "for the run's duration (0 = ephemeral, "
                        "-1 = no endpoint)")
    p.add_argument("--slo_p99_ms", type=float, default=0.0,
                   help="p99 latency SLO target in ms for the burn "
                        "watchdog (0 = no latency rule)")
    p.add_argument("--results_out", type=str, default=None,
                   help="write completed request results to this .npz "
                        "(one array per request id) — the replay "
                        "artifact serve_gate compares bit-for-bit")
    add_device_args(p)
    add_heal_args(p, checkpoint_every_default=2)
    return p


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)

    from arrow_matrix_tpu.cli.common import setup_platform

    setup_platform(args)

    import numpy as np

    from arrow_matrix_tpu.faults import RetryPolicy
    from arrow_matrix_tpu.obs import MetricsRegistry, flight
    from arrow_matrix_tpu.serve import (
        ArrowServer,
        ExecConfig,
        ba_executor_factory,
        run_trace,
        slo_summary,
        synthetic_trace,
        write_serve_artifacts,
    )

    registry = MetricsRegistry(run_dir=args.obs_dir)
    if args.obs_dir:
        import os

        os.makedirs(args.obs_dir, exist_ok=True)
        flight.install(os.path.join(args.obs_dir, "flight.json"))

    mesh = None
    if args.fmt == "ell":
        import jax

        from arrow_matrix_tpu.parallel import make_mesh

        n_dev = len(jax.devices())
        mesh = make_mesh((n_dev,), ("blocks",))
    factory, n_rows = ba_executor_factory(
        args.vertices, args.width, args.seed, fmt=args.fmt, mesh=mesh)
    base_cfg = ExecConfig(kernel=args.kernel,
                          overlap_slabs=args.overlap_slabs)
    policy = RetryPolicy.from_args(args)
    budget = (int(args.hbm_budget_mb * 2**20)
              if args.hbm_budget_mb > 0 else None)
    monitor, endpoint, tracer = None, None, None
    if args.pulse:
        import os

        from arrow_matrix_tpu.obs import Tracer, pulse as pulse_mod

        tracer = Tracer("graft-serve")
        ring = (os.path.join(args.obs_dir, "pulse_ring.json")
                if args.obs_dir else None)
        monitor = pulse_mod.PulseMonitor(
            window_s=args.pulse_window, ring_path=ring,
            name="graft-serve",
            watchdog=pulse_mod.SloWatchdog(pulse_mod.default_rules(
                target_p99_ms=(args.slo_p99_ms
                               if args.slo_p99_ms > 0 else None))))
    server = ArrowServer(
        factory, base_cfg, hbm_budget_bytes=budget,
        queue_capacity=args.queue, policy=policy,
        checkpoint_dir=args.checkpoint,
        checkpoint_every=args.checkpoint_every,
        max_batch_k=args.max_batch_k,
        degrade_after=args.degrade_after,
        registry=registry, tracer=tracer, name="graft-serve",
        verbose=True)
    if monitor is not None:
        server.attach_pulse(monitor)
        if args.pulse_port >= 0:
            from arrow_matrix_tpu.obs import PulseEndpoint

            endpoint = PulseEndpoint(monitor,
                                     port=args.pulse_port).start()
            print(f"graft-serve: pulse endpoint at {endpoint.url}"
                  f"/metrics", flush=True)
    trace = synthetic_trace(
        n_rows, tenants=args.tenants, requests=args.requests,
        k=args.features, iterations=args.iterations, seed=args.seed,
        deadline_s=args.deadline if args.deadline > 0 else None)
    t0 = time.perf_counter()
    tickets = run_trace(server, trace)
    wall = time.perf_counter() - t0
    if monitor is not None:
        monitor.close()
    summary = slo_summary(server, tickets, wall, pulse=monitor)

    lat = summary["latency_ms"]
    print(f"graft-serve: {summary['requests']} requests over "
          f"{args.tenants} tenants — {summary['completed']} completed,"
          f" {summary['shed']} shed, {summary['rejected']} rejected, "
          f"{summary['failed']} failed in {wall:.2f}s "
          f"({(summary['requests_per_s'] or 0):.2f} req/s)")
    if lat["count"]:
        print(f"graft-serve: latency p50={lat['p50']:.1f}ms "
              f"p90={lat['p90']:.1f}ms p99={lat['p99']:.1f}ms")
    hbm = summary["hbm"]
    print(f"graft-serve: hbm peak {hbm['peak_in_use_bytes']} / "
          f"{hbm['budget_bytes']} B "
          f"(peak occupancy {hbm['peak_occupancy']:.2e}; resident "
          f"operator {hbm['resident_bytes']} B)")
    if summary["faults_seen"]:
        print(f"graft-serve: {summary['faults_seen']} fault(s) seen, "
              f"{summary['recoveries']} recover(ies), "
              f"{summary['checkpoint_corruptions']} checkpoint "
              f"corruption(s) discarded")

    if monitor is not None:
        pt = summary["pulse"]
        burns = [e for e in pt["burn_events"]
                 if e["event"] == "slo_burn"]
        print(f"graft-serve: pulse — {len(pt['windows'])} windows of "
              f"{pt['window_s']}s, {len(burns)} SLO burn(s)"
              + (": " + ", ".join(sorted({b['rule'] for b in burns}))
                 if burns else ""), flush=True)
    if args.results_out:
        done = {t.request.request_id: t.result for t in tickets
                if t.result is not None}
        if monitor is not None:
            # Embed the windowed series in the replay artifact for
            # offline diffing.  Only with --pulse: serve_gate compares
            # fault vs fault-free artifacts file-by-file, and the
            # window series is timing-shaped, not replay-identical.
            done["_pulse_windows"] = np.frombuffer(
                json.dumps(summary["pulse"]["windows"]).encode(),
                dtype=np.uint8)
        np.savez(args.results_out, **done)
        print(f"graft-serve: wrote {len(done)} result(s) to "
              f"{args.results_out}")
    if args.obs_dir:
        import os

        if tracer is not None:
            tp = tracer.save(os.path.join(args.obs_dir,
                                          "serve_trace.json"))
            print(f"graft-serve: wrote request-correlated trace "
                  f"{tp}")
        if monitor is not None:
            with open(os.path.join(args.obs_dir,
                                   "pulse_metrics.prom"), "w",
                      encoding="utf-8") as fh:
                fh.write(monitor.exposition_text())
        path = write_serve_artifacts(args.obs_dir, summary,
                                     registry=registry)
        rec = flight.get_recorder()
        if rec is not None:
            rec.seal("graft-serve run complete")
            flight.set_recorder(None)
        print(f"graft-serve: wrote {path}")
    if endpoint is not None:
        endpoint.stop()
    if summary["failed"]:
        print(f"graft-serve: {summary['failed']} request(s) FAILED",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
