"""The autotune search loop (graft-tune).

``search()`` closes the loop the ISSUE-10 tentpole names: fingerprint
the structure (``tune/fingerprint.py``), short-circuit on a cached
plan (a second search of an unchanged graph spawns ZERO bench
children — the property ``tools/tune_gate.py`` verifies), otherwise
enumerate + prune the candidate space (``tune/space.py``), race the
survivors in subprocess-isolated children exactly the way ``bench.py``
races formats — each candidate in its own timeout-guarded process
with the flight recorder installed — and persist the winner as a
versioned :class:`~arrow_matrix_tpu.tune.plan.TunePlan`.

Eligibility is per traffic class (graft-classes).  For the default
``exact`` class a candidate may only WIN if its full-precision output
is bit-identical (``np.array_equal``, f32) to the golden
``ops/sell.py`` fold path — computed once in the parent as the default
executor's ``gather_result(step(x))`` on a seeded input, in original
row order.  The default configuration is itself always raced (and is
trivially bit-identical), so a winner always exists; candidates that
lose bit-identity (or are dtype experiments) are still timed and
recorded as diagnostics in the report.  For ``traffic_class="approx"``
a reduced-precision candidate may also win when its measured
single-step rel-Frobenius error is within the class tolerance
(``arrow_matrix_tpu/classes.py``) — and before such a winner is
persisted, its full error-vs-iteration curve is probed
(``ledger/probe.py``) and must certify (every point within tolerance);
the resulting certificate rides in the TunePlan.

Children are real subprocesses on purpose: a wedged compile or a
device grab costs ONE candidate its timeout, never the search; a
killed child leaves its flight-recorder ring behind
(``bench_cache/flight/tune_<candidate>.json``).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from arrow_matrix_tpu.tune.fingerprint import (
    fingerprint_hash,
    structure_fingerprint,
)
from arrow_matrix_tpu.tune.plan import (
    PLAN_VERSION,
    TunePlan,
    load_plan,
    save_plans,
)
from arrow_matrix_tpu.tune.space import Candidate, enumerate_candidates

#: Seed of the deterministic bit-identity input (shared parent/child).
GOLDEN_SEED = 3


def load_levels_from_source(source: dict):
    """Rebuild the decomposition a child (or the parent) searches
    over.  Two source kinds:

    * ``{"kind": "ba", "n", "m", "width", "seed", "max_levels"}`` —
      regenerate a Barabasi-Albert graph and decompose it (both fully
      seeded, so every process sees the identical structure);
    * ``{"kind": "dir", "base", "width"}`` — load a committed
      ``io/graphio.py`` artifact directory (the two bench_cache
      graphs ship with checked-in plans).

    Returns ``(levels, width)``.
    """
    kind = source.get("kind")
    if kind == "ba":
        from arrow_matrix_tpu.decomposition import arrow_decomposition
        from arrow_matrix_tpu.utils import barabasi_albert

        a = barabasi_albert(int(source["n"]), int(source.get("m", 3)),
                            seed=int(source["seed"]))
        width = int(source["width"])
        levels = arrow_decomposition(
            a, width, max_levels=int(source.get("max_levels", 10)),
            block_diagonal=True, seed=int(source["seed"]))
        return levels, width
    if kind == "dir":
        from arrow_matrix_tpu.io.graphio import (
            as_levels,
            load_decomposition,
            load_level_widths,
        )

        base = source["base"]
        width = source.get("width")
        loaded = load_decomposition(base, width, block_diagonal=True)
        widths = load_level_widths(base, width, len(loaded))
        levels = as_levels(loaded, widths)
        return levels, int(np.max(np.asarray(widths)))
    raise ValueError(f"unknown levels source kind {kind!r}")


def _build_executor(levels, width: int, cand: Candidate):
    """One candidate's executor over already-loaded levels (single
    chip — the tuned path is the fold/serve path, mesh=None)."""
    from arrow_matrix_tpu.parallel import MultiLevelArrow

    kwargs: Dict[str, Any] = {"fmt": "fold"}
    kwargs.update(cand.build)
    return MultiLevelArrow(levels, width, mesh=None,
                           kernel_opts=dict(cand.kernel_opts) or None,
                           **kwargs)


def _flight_install(name: str) -> None:
    """Best-effort black-box recorder in a tune child (bench.py's
    ``_install_flight`` contract: a SIGKILLed child still leaves its
    last-known state on disk)."""
    try:
        from arrow_matrix_tpu.obs import flight

        path = os.path.join(
            os.environ.get("AMT_FLIGHT_DIR",
                           os.path.join("bench_cache", "flight")),
            f"{name}.json")
        flight.install(path)
    except Exception as e:  # noqa: BLE001 — never cost the measurement
        print(f"[tune] flight recorder unavailable: "
              f"{type(e).__name__}: {e}", file=sys.stderr)


def candidate_child_main(cfg: dict) -> dict:
    """Body of one candidate subprocess (``python -m
    arrow_matrix_tpu.tune --candidate <name>``): build, verify
    bit-identity vs the parent's golden artifact, measure ms/iter.
    Prints nothing itself — the caller emits the returned dict as the
    final JSON line (``utils/artifacts.parse_last_json_line`` contract).
    """
    from arrow_matrix_tpu.utils.platform import (
        enable_compile_cache,
        force_cpu_devices,
    )

    if cfg.get("platform") == "cpu":
        force_cpu_devices()
    enable_compile_cache()
    name = cfg["candidate"]["name"]
    _flight_install(f"tune_{name}")
    from arrow_matrix_tpu.obs import chained_iteration_ms
    from arrow_matrix_tpu.utils.graphs import random_dense

    cand = Candidate(name, build=cfg["candidate"].get("build") or {},
                     kernel_opts=cfg["candidate"].get("kernel_opts")
                     or {})
    levels, width = load_levels_from_source(cfg["source"])
    multi = _build_executor(levels, width, cand)
    k = int(cfg["k"])
    x_host = random_dense(multi.n, k, seed=GOLDEN_SEED)
    x = multi.set_features(x_host)

    bit_identical = None
    rel_frobenius = None
    golden_path = cfg.get("golden_path")
    if cfg.get("write_golden"):
        # The golden is the default fold executor stepped once — on the
        # device, so it runs here in a child like every candidate.
        np.save(golden_path, np.asarray(
            multi.gather_result(multi.step(x)), dtype=np.float32))
        bit_identical, rel_frobenius = True, 0.0
    elif golden_path:
        golden = np.load(golden_path)
        mine = np.asarray(multi.gather_result(multi.step(x)),
                          dtype=np.float32)
        bit_identical = bool(np.array_equal(mine, golden))
        # Single-step rel-Frobenius vs the golden: the approx-class
        # eligibility screen (the full curve certifies the winner).
        gn = float(np.linalg.norm(golden.astype(np.float64)))
        diff = float(np.linalg.norm(mine.astype(np.float64)
                                    - golden.astype(np.float64)))
        rel_frobenius = diff / gn if gn > 0 else diff

    ms = chained_iteration_ms(multi.run, x, int(cfg.get("iters", 3)))
    return {"name": name, "ms": round(float(ms), 4),
            "bit_identical": bit_identical,
            "rel_frobenius": rel_frobenius}


def _spawn_tune_candidate(cand: Candidate, cfg: dict,
                          timeout_s: float) -> dict:
    """One candidate subprocess -> its parsed JSON (or an error dict);
    every failure shape is contained to the returned dict, the
    ``bench.py _spawn_candidate`` contract."""
    from arrow_matrix_tpu.utils.artifacts import parse_last_json_line

    child_cfg = dict(cfg)
    child_cfg["candidate"] = {"name": cand.name, "build": cand.build,
                              "kernel_opts": cand.kernel_opts}
    from arrow_matrix_tpu.utils.platform import compile_cache_env

    env = compile_cache_env(dict(os.environ,
                                 AMT_TUNE_CFG=json.dumps(child_cfg)))
    try:
        proc = subprocess.run(
            [sys.executable, "-m", "arrow_matrix_tpu.tune",
             "--candidate", cand.name],
            capture_output=True, text=True, timeout=timeout_s, env=env)
    except subprocess.TimeoutExpired:
        err: Dict[str, Any] = {"name": cand.name,
                               "error": f"timed out after "
                                        f"{timeout_s:.0f}s",
                               "timed_out": True}
        fp = os.path.join(
            os.environ.get("AMT_FLIGHT_DIR",
                           os.path.join("bench_cache", "flight")),
            f"tune_{cand.name}.json")
        if os.path.exists(fp):
            err["flight"] = fp
        return err
    if proc.returncode != 0 or not proc.stdout.strip():
        return {"name": cand.name,
                "error": f"rc={proc.returncode}: "
                         f"{proc.stderr.strip()[-400:]}"}
    rec = parse_last_json_line(proc.stdout)
    if rec is None:
        return {"name": cand.name,
                "error": f"unusable child output: "
                         f"{proc.stdout.strip()[-200:]}"}
    return rec


def _certify_candidate(source: dict, dtype: str, k: int,
                       ledger_dir: Optional[str], say) :
    """Probe the full error-vs-iteration curve for one carriage dtype
    and derive its :class:`~arrow_matrix_tpu.classes.Certificate`
    (recorded in the ledger when one is configured); None when the
    probe fails."""
    from arrow_matrix_tpu.classes import certificate_from_record
    from arrow_matrix_tpu.ledger.probe import error_curves_for_source

    try:
        ledger = None
        if ledger_dir is not None:
            from arrow_matrix_tpu.ledger.store import Ledger

            ledger = Ledger(ledger_dir)
        recs = error_curves_for_source(source, k=int(k),
                                       dtypes=(dtype,), ledger=ledger)
        return certificate_from_record(recs[0])
    except Exception as e:  # noqa: BLE001 — a failed probe fails the
        say(f"certificate probe failed: {type(e).__name__}: {e}")
        return None         # candidate, never the search


def _plan_from_candidate(cand: Candidate, h: str, k: int) -> TunePlan:
    """Fold a candidate's overrides over the default knob set."""
    base = TunePlan(structure_hash=h, k=int(k)).to_dict()
    base.update({kk: v for kk, v in cand.build.items()})
    base.update({kk: v for kk, v in cand.kernel_opts.items()})
    base["candidate"] = cand.name
    return TunePlan.from_dict(base)


def search(source: dict, k: int, *, iters: int = 3,
           timeout_s: float = 240.0, dtype=np.float32,
           plan_dir: Optional[str] = None, refresh: bool = False,
           allow_int8: bool = False,
           restrict: Optional[List[str]] = None,
           run_dir: Optional[str] = None,
           ledger_dir: Optional[str] = None,
           traffic_class: str = "exact",
           extra: Optional[List[Candidate]] = None,
           lens_model=None,
           synth: bool = False,
           quiet: bool = False) -> Tuple[Optional[TunePlan], dict]:
    """Search (or cache-hit) the tuned plan for one (structure, k).

    Returns ``(plan, report)``.  ``report["cache_hit"]`` /
    ``report["children_spawned"]`` are the gate's purity evidence: an
    unchanged graph's second search is a pure cache hit with zero
    children.  ``refresh=True`` forces a re-search.  ``ledger_dir``
    redirects the winner's graft-ledger record (smoke runs pass a
    run-dir-local store).

    ``traffic_class="approx"`` admits tolerance-gated reduced-precision
    winners (module docstring); the cached plan records the class, so
    an exact consumer never silently inherits an approx plan
    (``load_plan`` keys on k within one structure file — approx
    searches should use a distinct ``plan_dir`` or consume the plan
    object directly, as ``serve/scheduler.ArrowServer`` does).

    ``extra`` forwards caller-supplied candidates (generated
    programs) to ``enumerate_candidates``; pallas extras must pass
    graft-kcert certification there or they are pruned with zero
    children spawned.

    ``lens_model`` (an ``obs.costmodel.CostModel``, or a path to its
    JSON artifact) arms the graft-lens compute screen in
    ``enumerate_candidates``: compute-hopeless candidates are pruned
    with ``"lens: …"`` reasons before their child spawns.

    ``synth=True`` arms graft-synth: per-level schedules derived from
    the degree-ladder fingerprint (``tune/synth.synth_candidates``)
    join the race through ``extra`` — same kcert/lens screens, same
    f32 bit-identity win rule — and the surviving generated program is
    persisted in the synth store so graft-kcert certifies it in every
    later process.  A cache hit still short-circuits BEFORE synthesis:
    purity (zero children) covers generated programs too.
    """
    from arrow_matrix_tpu.classes import tolerance_for
    from arrow_matrix_tpu.utils.platform import host_load

    def _say(msg: str) -> None:
        if not quiet:
            print(f"[graft-tune] {msg}", file=sys.stderr, flush=True)

    t0 = time.perf_counter()
    levels, width = load_levels_from_source(source)
    fp = structure_fingerprint(levels, width, dtype=dtype)
    h = fingerprint_hash(fp)
    _say(f"structure {h} (n={fp['n']}, total_rows={fp['total_rows']}, "
         f"{len(fp['ladder']['rows'])} tiers)")

    if not refresh:
        cached = load_plan(h, k, plan_dir, quiet=True)
        if cached is not None and cached.traffic_class != traffic_class:
            _say(f"cached plan is {cached.traffic_class!r}, search "
                 f"wants {traffic_class!r}: re-searching")
            cached = None
        if cached is not None:
            _say(f"cache HIT for k={k}: candidate "
                 f"{cached.candidate!r} ({cached.measured_ms} ms, "
                 f"margin {cached.margin})")
            return cached, {
                "structure_hash": h, "k": int(k), "cache_hit": True,
                "children_spawned": 0,
                "lookup_ms": round((time.perf_counter() - t0) * 1e3, 3),
                "plan": cached.to_dict(),
            }

    # One process per chip: the platform comes from a discovery child,
    # never from a backend in this (parent) process.
    from arrow_matrix_tpu.utils.platform import child_platform

    found = child_platform()
    platform = found["platform"]
    evaluator = "cpu-interpret" if platform == "cpu" else platform

    if isinstance(lens_model, (str, os.PathLike)):
        import json as _json

        from arrow_matrix_tpu.obs.costmodel import CostModel
        with open(lens_model, "r", encoding="utf-8") as fh:
            lens_model = CostModel.from_dict(_json.load(fh))
    if synth:
        from arrow_matrix_tpu.tune import synth as _synth

        generated = _synth.synth_candidates(fp,
                                            traffic_class=traffic_class)
        if generated:
            _say(f"synth: {len(generated)} generated candidate(s): "
                 + "; ".join(f"{c.name} [{_synth.schedule_summary(c.kernel_opts['schedule'])}]"
                             for c in generated))
            extra = list(extra or []) + generated
    cands, pruned = enumerate_candidates(
        fp, k, platform=platform,
        allow_int8=allow_int8, restrict=restrict, traffic_class=traffic_class, extra=extra,
        lens_model=lens_model)
    for name, why in pruned.items():
        _say(f"pruned {name}: {why}")

    synth_program = None
    if synth:
        # Persist + register the generated exact program ONLY when it
        # survived the kcert/lens screens — the committed store must
        # hold nothing `analysis kernels --check` would flag.
        for c in cands:
            if c.name == "synth_ladder":
                synth_program = _synth.persist_program(
                    fp, h, k, c.kernel_opts["schedule"])
                _say(f"synth: persisted generated program "
                     f"{synth_program}")
                break

    run_dir = run_dir or os.path.join("bench_cache", "tune_runs", h)
    os.makedirs(run_dir, exist_ok=True)
    golden_path = os.path.join(run_dir, f"golden_k{int(k)}.npy")
    cfg = {"source": source, "k": int(k), "iters": int(iters),
           "golden_path": os.path.abspath(golden_path),
           "platform": platform}
    # The default candidate's child writes the golden first; every
    # other candidate is compared against it.
    if not any(c.name == "default" for c in cands):
        gold = _spawn_tune_candidate(Candidate("default"),
                                     dict(cfg, write_golden=True),
                                     timeout_s)
        if gold.get("error"):
            raise RuntimeError(f"golden run failed: {gold['error']}")
    results: Dict[str, dict] = {}
    for cand in sorted(cands, key=lambda c: c.name != "default"):
        _say(f"racing {cand.name}")
        results[cand.name] = _spawn_tune_candidate(
            cand, dict(cfg, write_golden=cand.name == "default"),
            timeout_s)
        r = results[cand.name]
        _say(f"  {cand.name}: ms={r.get('ms')} "
             f"bit_identical={r.get('bit_identical')} "
             f"err={r.get('error')}")

    default_ms = results.get("default", {}).get("ms")

    def _effective_dtype(c: Candidate) -> Optional[str]:
        """The accuracy-class key of a candidate's carriage: build or
        kernel_opts ``feature_dtype``, or — for a graft-synth per-level
        schedule — the NARROWEST per-tier carriage (the whole output
        is only as exact as its least exact tier)."""
        fd = (c.build.get("feature_dtype")
              or c.kernel_opts.get("feature_dtype"))
        if fd is None and c.kernel_opts.get("schedule"):
            carrs = {e.get("carriage", "f32")
                     for e in c.kernel_opts["schedule"]}
            for narrow in ("int8", "bf16"):
                if narrow in carrs:
                    return narrow
        return fd

    def _class_ok(c: Candidate) -> bool:
        r = results[c.name]
        if (r.get("error") is not None or r.get("ms") is None):
            return False
        if r.get("bit_identical") is True:
            return True
        if traffic_class != "approx":
            return False
        # Approx class: a reduced-precision candidate passes the
        # screen when its single-step error is within the class
        # tolerance; the full curve still has to certify below.
        fd = _effective_dtype(c)
        rel = r.get("rel_frobenius")
        return (fd is not None and rel is not None
                and rel <= tolerance_for(fd))

    eligible = [c for c in cands if c.eligible and _class_ok(c)]
    certificate = None
    winner = None
    while eligible:
        pick = min(eligible, key=lambda c: results[c.name]["ms"])
        fd = _effective_dtype(pick)
        if (traffic_class != "approx" or fd is None
                or results[pick.name].get("bit_identical") is True):
            winner = pick
            break
        # Reduced-precision approx winner: probe the full
        # error-vs-iteration curve before persisting — the curve IS
        # the certificate a serve-time admission decision trusts.
        cert = _certify_candidate(source, fd, k, ledger_dir, _say)
        if cert is not None and cert.covers(cert.iterations):
            winner, certificate = pick, cert
            break
        _say(f"{pick.name}: curve failed to certify "
             f"(tolerance {tolerance_for(fd)}) — dropping candidate")
        eligible.remove(pick)
    if winner is None:
        _say("no eligible candidate (default failed?) — no plan saved")
        return None, {
            "structure_hash": h, "k": int(k), "cache_hit": False,
            "children_spawned": len(cands), "results": results,
            "pruned": pruned, "error": "no eligible candidate",
            "synth_program": synth_program,
        }
    w_ms = float(results[winner.name]["ms"])
    margin = (None if not default_ms
              else round((float(default_ms) - w_ms) / float(default_ms),
                         4))
    plan = _plan_from_candidate(winner, h, k)
    plan = TunePlan.from_dict({
        **plan.to_dict(),
        "measured_ms": w_ms,
        "default_ms": default_ms,
        "margin": margin,
        "bit_identical":
            results[winner.name].get("bit_identical") is True,
        "host_load": host_load(),
        "platform": platform,
        "evaluator": evaluator,
        "created_unix": round(time.time(), 3),
        "traffic_class": traffic_class,
        "certificate": certificate.to_dict() if certificate else None,
    })
    path = save_plans(h, {int(k): plan}, fingerprint=fp,
                      directory=plan_dir,
                      context={"source": source, "iters": int(iters)})
    _say(f"winner {winner.name!r}: {w_ms} ms vs default {default_ms} "
         f"(margin {margin}); saved {path}")
    # graft-ledger: the winner + margin also land in the append-only
    # store, keyed by the same structure hash as the plan cache.
    try:
        from arrow_matrix_tpu.ledger import record as _ledger_record

        _ledger_record(
            "tune", f"tuned_spmm_ms_k{int(k)}", w_ms, unit="ms",
            directory=ledger_dir,
            structure_hash=h, platform=platform,
            device_kind="host" if platform == "cpu" else platform,
            host_load=plan.host_load.get("loadavg_1m")
            if isinstance(plan.host_load, dict) else None,
            knobs={"k": int(k), "candidate": winner.name,
                   "kernel": plan.kernel, "fmt": plan.fmt,
                   "chunk": plan.chunk,
                   "feature_dtype": plan.feature_dtype,
                   "traffic_class": traffic_class},
            payload={"default_ms": default_ms, "margin": margin,
                     "bit_identical": plan.bit_identical,
                     "evaluator": evaluator,
                     "source": source, "plan_path": path})
    except Exception as e:
        _say(f"ledger record not persisted: {type(e).__name__}: {e}")
    return plan, {
        "structure_hash": h, "k": int(k), "cache_hit": False,
        "children_spawned": len(cands), "results": results,
        "pruned": pruned, "winner": winner.name,
        "plan": plan.to_dict(), "plan_path": path,
        "synth_program": synth_program,
        "wall_s": round(time.perf_counter() - t0, 3),
    }


def smoke_tune(run_dir: str, *, n: int = 96, width: int = 16,
               seed: int = 3, k: int = 8, iters: int = 2,
               timeout_s: float = 180.0,
               plan_dir: Optional[str] = None,
               restrict: Optional[List[str]] = None,
               quiet: bool = True) -> dict:
    """One tiny end-to-end search on a seeded BA graph — the
    amt_doctor TUNE probe and the tier-1 tests ride this (3 children,
    host CPU).  Returns the search report with the plan embedded."""
    if plan_dir is None:
        plan_dir = os.path.join(run_dir, "tune_plans")
    if restrict is None:
        restrict = ["default", "fold_tight", "chunk_4096"]
    source = {"kind": "ba", "n": int(n), "m": 3, "width": int(width),
              "seed": int(seed), "max_levels": 4}
    plan, report = search(source, k, iters=iters, timeout_s=timeout_s,
                          plan_dir=plan_dir, restrict=restrict,
                          run_dir=os.path.join(run_dir, "tune_runs"),
                          ledger_dir=os.path.join(run_dir, "ledger"),
                          quiet=quiet)
    report["plan_version"] = PLAN_VERSION
    report["ok"] = plan is not None
    return report
